"""Public pack/unpack ops with pallas/jnp dispatch."""
from ...core.backend import pallas_interpret
from .bitpack import pack_bits_pallas, unpack_bits_pallas  # noqa: F401
from .ref import pack_ref, unpack_ref  # noqa: F401


def pack(bits, *, use_pallas=True):
    if use_pallas:
        return pack_bits_pallas(bits, interpret=pallas_interpret())
    return pack_ref(bits)


def unpack(words, *, use_pallas=True):
    if use_pallas:
        return unpack_bits_pallas(words, interpret=pallas_interpret())
    return unpack_ref(words)
