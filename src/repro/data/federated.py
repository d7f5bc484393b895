"""Device-resident federated dataset — the scan engine's data layer.

The host-loop engines fed each round from Python callbacks
(``client_batch_fn(rnd, cid)`` + ``stack_client_batches``): one numpy
fancy-index, one host→device transfer, and one stack per round.  A
multi-round ``lax.scan`` program cannot call back into Python, so the
whole dataset moves onto the device ONCE:

  - ``x, y``            stacked example arrays, (N, ...) device-resident;
  - ``client_idx``      (C, Lmax) int32 partition matrix — row c lists
                        client c's example indices, wrap-padded to the
                        longest client so the matrix is rectangular;
  - ``client_len``      (C,) int32 true partition sizes (sampling draws
                        positions modulo the real length, so the padding
                        is never sampled).

``gather_batches(round_idx, picked)`` is the in-program replacement for
the host batch path: a pure jax function ``(round_idx, picked) ->
(K, S, B, ...)`` batches, traceable inside jit / scan.  Batch positions
derive from ``fold_in(fold_in(key(batch_seed), round_idx), cid)``, so the
same (round, client) always yields the same batch — on the host (legacy
``batch_fn`` adapter, used by the looped/batched engines) and inside the
scan program alike.  That shared derivation is what makes the three
engines' trajectories bit-comparable.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Pytree = Any


@dataclasses.dataclass(frozen=True)
class FederatedDataset:
    """Stacked examples + partition index matrices, all device-resident."""

    x: jax.Array                    # (N, ...) examples
    y: jax.Array                    # (N,) labels / targets
    client_idx: jax.Array           # (C, Lmax) int32, wrap-padded rows
    client_len: jax.Array           # (C,) int32 true sizes
    x_test: Optional[jax.Array]     # (Nt, ...) device-resident test set
    y_test: Optional[jax.Array]     # (Nt,)
    batch_seed: int = 0

    @property
    def num_clients(self) -> int:
        return self.client_idx.shape[0]

    # ---- in-program batch gather -------------------------------------

    def _client_key(self, round_idx, cid) -> jax.Array:
        key = jax.random.key(self.batch_seed)
        key = jax.random.fold_in(key, round_idx)
        return jax.random.fold_in(key, cid)

    def client_batch(self, round_idx, cid, *, steps: int,
                     batch: int) -> Tuple[jax.Array, jax.Array]:
        """(S, B, ...) local batches for one client — pure, traceable."""
        key = self._client_key(round_idx, cid)
        pos = jax.random.randint(key, (steps, batch), 0,
                                 self.client_len[cid])
        take = self.client_idx[cid, pos]
        return self.x[take], self.y[take]

    def gather_batches(self, round_idx, picked, *, steps: int,
                       batch: int) -> Tuple[jax.Array, jax.Array]:
        """(K, S, B, ...) batches for the picked clients, in-program.

        ``picked`` is a (K,) int32 array; ``round_idx`` may be traced
        (it is the scan counter inside the experiment program).
        """
        with jax.named_scope("fed.gather"):
            return jax.vmap(lambda c: self.client_batch(
                round_idx, c, steps=steps, batch=batch))(picked)

    # ---- legacy host adapter -----------------------------------------

    def batch_fn(self, *, steps: int, batch: int) -> Callable[[int, int], Any]:
        """``client_batch_fn(rnd, cid)`` adapter for the host-loop engines.

        Same key derivation ⇒ identical batch values to the in-program
        gather; jitted so repeated host calls stay cheap.
        """
        fn = jax.jit(lambda r, c: self.client_batch(
            r, c, steps=steps, batch=batch))
        return lambda rnd, cid: fn(jnp.int32(rnd), jnp.int32(cid))

    # ---- the streaming tier ------------------------------------------

    def cohorted(self, cohort_size: int) -> "CohortedDataset":
        """This population re-sharded into host cohorts for the cohort
        engine (``Experiment.run(engine="cohort")``)."""
        return CohortedDataset.from_federated(self, cohort_size)


def _as_parts_list(parts) -> List[np.ndarray]:
    """Normalize a partition spec to a list of per-client index arrays.

    Accepts the partitioner output (a sequence of 1-D arrays) or — the
    population-scale fast path — a 2-D ``(C, L)`` array meaning C clients
    of uniform length L (``make_cohorted_dataset`` at C = 1e6 cannot
    afford a million tiny-array concatenations).
    """
    if isinstance(parts, np.ndarray) and parts.ndim == 2:
        return parts          # handled vectorized by the cohort builder
    return [np.asarray(p, np.int64) for p in parts]


# ---------------------------------------------------------------------------
# cohort-sharded populations: the streaming tier's data layer
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class CohortShard:
    """One cohort's host-side slice of the population.

    ``idx`` is the wrap-padded index matrix in COHORT-LOCAL example
    coordinates (rows index into ``ex_idx`` order), padded only to this
    cohort's own longest client — under client-size skew the giant
    client inflates one shard's matrix instead of all of them
    (the whole-population matrix is C × global-Lmax).
    """

    clients: np.ndarray     # (Cc,) int32 global client ids
    ex_idx: np.ndarray      # (Ne,) int64 global example rows, client-major
    idx: np.ndarray         # (Cc, Lc) int32 cohort-local wrap-padded rows
    lens: np.ndarray        # (Cc,) int32 true client sizes

    @property
    def num_clients(self) -> int:
        return self.idx.shape[0]

    @property
    def num_examples(self) -> int:
        return self.ex_idx.shape[0]

    @property
    def lmax(self) -> int:
        return self.idx.shape[1]


@dataclasses.dataclass(frozen=True)
class CohortedDataset:
    """A client population sharded into host-resident cohorts.

    The streaming counterpart of :class:`FederatedDataset`: examples and
    index matrices stay in HOST numpy, sharded by cohort (a contiguous
    block of ``cohort_size`` clients), and :meth:`stage` moves ONE
    cohort's block to the device — padded to the population-wide maxima
    so every cohort shares a single compiled program shape.  The cohort
    engine (``fed/engine.make_cohort_engine``) double-buffers these
    blocks host→device while the current cohort's round program runs,
    which is what lets C = 1e5–1e6 simulated clients run on a device
    that could never hold the whole population.

    Batch-key derivation is identical to :class:`FederatedDataset`
    (keys fold the GLOBAL client id; index rows are cohort-local), so
    cohort-partitioned gathers equal whole-population gathers exactly.
    """

    x: np.ndarray                   # (N, ...) host examples
    y: np.ndarray                   # (N,) host labels
    shards: Tuple[CohortShard, ...]
    cohort_of: np.ndarray           # (C,) int32 client -> cohort id
    local_of: np.ndarray            # (C,) int32 client -> index in cohort
    x_test: Optional[jax.Array]     # device-resident (tiny next to x)
    y_test: Optional[jax.Array]
    batch_seed: int = 0

    @property
    def num_clients(self) -> int:
        return self.cohort_of.shape[0]

    @property
    def num_cohorts(self) -> int:
        return len(self.shards)

    # staging pads: one compiled program shape across ALL cohorts
    @property
    def pad_clients(self) -> int:
        return max(s.num_clients for s in self.shards)

    @property
    def pad_examples(self) -> int:
        return max(s.num_examples for s in self.shards)

    @property
    def pad_len(self) -> int:
        return max(s.lmax for s in self.shards)

    def stage(self, j: int) -> Dict[str, jax.Array]:
        """Cohort ``j``'s device block, padded to the population maxima.

        Padding rows get ``client_len = 1`` (a zero bound would break the
        in-program ``randint``) and index row 0 — they are only ever
        gathered for slots the engine weights/masks to zero.  This host
        slice-and-pad + transfer is exactly the work the cohort engine's
        prefetch thread hides behind the previous cohort's compute.
        """
        s = self.shards[j]
        xs = np.zeros((self.pad_examples,) + self.x.shape[1:], self.x.dtype)
        ys = np.zeros((self.pad_examples,) + self.y.shape[1:], self.y.dtype)
        xs[:s.num_examples] = self.x[s.ex_idx]
        ys[:s.num_examples] = self.y[s.ex_idx]
        idx = np.zeros((self.pad_clients, self.pad_len), np.int32)
        idx[:s.num_clients, :s.lmax] = s.idx
        lens = np.ones((self.pad_clients,), np.int32)
        lens[:s.num_clients] = s.lens
        return {"x": jax.device_put(jnp.asarray(xs)),
                "y": jax.device_put(jnp.asarray(ys)),
                "client_idx": jax.device_put(jnp.asarray(idx)),
                "client_len": jax.device_put(jnp.asarray(lens))}

    @classmethod
    def from_federated(cls, ds: FederatedDataset,
                       cohort_size: int) -> "CohortedDataset":
        """Re-shard a device-resident dataset into host cohorts."""
        idx = np.asarray(ds.client_idx)
        lens = np.asarray(ds.client_len)
        parts = [idx[c, :lens[c]] for c in range(ds.num_clients)]
        return make_cohorted_dataset(
            np.asarray(ds.x), np.asarray(ds.y), parts,
            cohort_size=cohort_size, x_test=ds.x_test, y_test=ds.y_test,
            batch_seed=ds.batch_seed)


def cohort_gather(block: Dict[str, jax.Array], round_idx, cids, locs,
                  *, steps: int, batch: int,
                  batch_seed: int) -> Tuple[jax.Array, jax.Array]:
    """(K, S, B, ...) batches for picked clients out of ONE staged cohort.

    Pure/traceable; the cohort-tier replacement for
    ``FederatedDataset.gather_batches``.  ``cids`` carries GLOBAL client
    ids (the batch key folds them, preserving whole-population key
    parity) while ``locs`` carries the cohort-LOCAL rows the staged
    index matrix is addressed by.
    """

    def one(cid, loc):
        key = jax.random.key(batch_seed)
        key = jax.random.fold_in(key, round_idx)
        key = jax.random.fold_in(key, cid)
        pos = jax.random.randint(key, (steps, batch), 0,
                                 block["client_len"][loc])
        take = block["client_idx"][loc, pos]
        return block["x"][take], block["y"][take]

    return jax.vmap(one)(cids, locs)


def make_cohorted_dataset(
    x: np.ndarray,
    y: np.ndarray,
    parts,
    *,
    cohort_size: int,
    x_test: Optional[np.ndarray] = None,
    y_test: Optional[np.ndarray] = None,
    batch_seed: int = 0,
) -> CohortedDataset:
    """Shard a partitioned task into host-resident cohorts.

    ``parts`` is the partitioner output (one index array per client) or a
    2-D ``(C, L)`` array for uniform-size clients — the vectorized path
    population-scale synthetic benchmarks need.  Clients are assigned to
    cohorts contiguously: cohort ``j`` holds clients
    ``[j·cohort_size, (j+1)·cohort_size)``.
    """
    if cohort_size < 1:
        raise ValueError(f"cohort_size must be >= 1, got {cohort_size}")
    parts = _as_parts_list(parts)
    uniform = isinstance(parts, np.ndarray)
    C = parts.shape[0] if uniform else len(parts)
    if C == 0:
        raise ValueError("need at least one client")
    x = np.asarray(x)
    y = np.asarray(y)
    shards = []
    for c0 in range(0, C, cohort_size):
        c1 = min(c0 + cohort_size, C)
        if uniform:
            lens = np.full((c1 - c0,), parts.shape[1], np.int64)
            ex_idx = np.asarray(parts[c0:c1], np.int64).reshape(-1)
        else:
            plist = parts[c0:c1]
            lens = np.array([len(p) for p in plist], np.int64)
            if (lens <= 0).any():
                raise ValueError("every client needs at least one example")
            ex_idx = (np.concatenate(plist) if plist else
                      np.zeros((0,), np.int64))
        off = np.zeros_like(lens)
        np.cumsum(lens[:-1], out=off[1:])
        lc = int(lens.max())
        # wrap-padding in cohort-local coordinates: row c cycles client
        # c's own examples, exactly like make_federated_dataset's
        # np.resize rows (positions < client_len never see the padding)
        grid = np.arange(lc, dtype=np.int64)[None, :]
        idx = (off[:, None] + grid % lens[:, None]).astype(np.int32)
        shards.append(CohortShard(
            clients=np.arange(c0, c1, dtype=np.int32), ex_idx=ex_idx,
            idx=idx, lens=lens.astype(np.int32)))
    ids = np.arange(C, dtype=np.int32)
    return CohortedDataset(
        x=x, y=y, shards=tuple(shards),
        cohort_of=ids // np.int32(cohort_size),
        local_of=ids % np.int32(cohort_size),
        x_test=None if x_test is None else jnp.asarray(x_test),
        y_test=None if y_test is None else jnp.asarray(y_test),
        batch_seed=batch_seed)


def make_federated_dataset(
    x: np.ndarray,
    y: np.ndarray,
    parts: Sequence[np.ndarray],
    *,
    x_test: Optional[np.ndarray] = None,
    y_test: Optional[np.ndarray] = None,
    batch_seed: int = 0,
) -> FederatedDataset:
    """Stack a partitioned task onto the device.

    ``parts`` is the partitioner output (one index array per client, as
    from :func:`repro.data.make_partition`).  Rows of the index matrix are
    wrap-padded (cycled) to the longest client so a rectangular int32
    matrix can live on device; sampling never reads the padding because
    positions are drawn in ``[0, client_len)``.
    """
    lens = np.array([len(p) for p in parts], np.int32)
    if (lens <= 0).any():
        raise ValueError("every client needs at least one example")
    lmax = int(lens.max())
    idx = np.stack([np.resize(np.asarray(p, np.int64), lmax)
                    for p in parts]).astype(np.int32)
    return FederatedDataset(
        x=jnp.asarray(x), y=jnp.asarray(y),
        client_idx=jnp.asarray(idx), client_len=jnp.asarray(lens),
        x_test=None if x_test is None else jnp.asarray(x_test),
        y_test=None if y_test is None else jnp.asarray(y_test),
        batch_seed=batch_seed)
