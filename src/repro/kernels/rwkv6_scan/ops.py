"""Public wkv op with pallas/jnp dispatch."""
from ...core.backend import pallas_interpret
from .ref import wkv_ref
from .rwkv6_scan import wkv_pallas


def wkv(r, k, v, w, u, s0, *, use_pallas=True):
    if use_pallas:
        return wkv_pallas(r, k, v, w, u, s0, interpret=pallas_interpret())
    return wkv_ref(r, k, v, w, u, s0)
