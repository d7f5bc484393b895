"""The one traffic generator: reads a mix's parameters and makes the
federation's inputs from them.

- ``partition``: which training examples each client holds.  The
  partitioners follow the paper's section 5.1.2 (IID, Non-IID-1 =
  Dirichlet label skew, Non-IID-2 = a few labels per client) and are
  copies of the program's ``repro.data.synthetic`` ones, kept here so
  that no later change of the program changes the benchmark's inputs.
- ``schedule_rows``: the clients picked each round, the sequence the
  program's ``make_client_schedule`` draws (``RandomState(seed)``, one
  ``choice`` without replacement per round).
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np


def partition_iid(seed: int, n: int, num_clients: int) -> List[np.ndarray]:
    rng = np.random.RandomState(seed)
    idx = rng.permutation(n)
    return [np.sort(s) for s in np.array_split(idx, num_clients)]


def partition_dirichlet(seed: int, labels: np.ndarray, num_clients: int,
                        alpha: float) -> List[np.ndarray]:
    """Non-IID-1: per-label client proportions ~ Dir(alpha)."""
    rng = np.random.RandomState(seed)
    n_classes = int(labels.max()) + 1
    out: List[List[int]] = [[] for _ in range(num_clients)]
    for c in range(n_classes):
        idx = np.where(labels == c)[0]
        rng.shuffle(idx)
        props = rng.dirichlet([alpha] * num_clients)
        cuts = (np.cumsum(props) * len(idx)).astype(int)[:-1]
        for cid, part in enumerate(np.split(idx, cuts)):
            out[cid].extend(part.tolist())
    # every client gets at least one example, from a donor that keeps one
    for cid in range(num_clients):
        if not out[cid]:
            donors = [i for i in range(num_clients) if len(out[i]) > 1]
            donor = max(donors, key=lambda i: len(out[i]))
            out[cid].append(out[donor].pop())
    return [np.sort(np.array(o, dtype=np.int64)) for o in out]


def partition_labels(seed: int, labels: np.ndarray, num_clients: int,
                     labels_per_client: int) -> List[np.ndarray]:
    """Non-IID-2: each client holds only ``labels_per_client`` labels."""
    rng = np.random.RandomState(seed)
    n_classes = int(labels.max()) + 1
    deck: List[int] = []
    while len(deck) < num_clients * labels_per_client:
        deck.extend(rng.permutation(n_classes).tolist())
    client_labels: List[List[int]] = []
    for cid in range(num_clients):
        ls: List[int] = []
        for lab in deck[cid * labels_per_client:]:
            if lab not in ls:
                ls.append(lab)
            if len(ls) == labels_per_client:
                break
        client_labels.append(ls)
    owners_of: Dict[int, List[int]] = {c: [] for c in range(n_classes)}
    for cid, ls in enumerate(client_labels):
        for lab in ls:
            owners_of[int(lab)].append(cid)
    out: List[List[int]] = [[] for _ in range(num_clients)]
    for c in range(n_classes):
        idx = np.where(labels == c)[0]
        rng.shuffle(idx)
        owners = owners_of[c] or [int(rng.randint(num_clients))]
        for k, part in enumerate(np.array_split(idx, len(owners))):
            out[owners[k]].extend(part.tolist())
    return [np.sort(np.array(o, dtype=np.int64)) for o in out]


def partition(traffic: dict, labels: np.ndarray,
              seed: int) -> List[np.ndarray]:
    """The mix's partition of ``labels`` over its clients."""
    p = traffic["partition"]
    kind, c = p["kind"], traffic["num_clients"]
    if kind == "iid":
        return partition_iid(seed, len(labels), c)
    if kind == "noniid1":
        return partition_dirichlet(seed, labels, c, p["alpha"])
    if kind == "noniid2":
        return partition_labels(seed, labels, c, p["labels_per_client"])
    raise ValueError(f"unknown partition kind {kind!r}")


def schedule_rows(seed: int, num_clients: int, k: int, rounds: int):
    """``(rounds, k)`` int32 client ids, round by round."""
    rng = np.random.RandomState(seed)
    return np.stack([rng.choice(num_clients, k, replace=False)
                     for _ in range(rounds)]).astype(np.int32)

