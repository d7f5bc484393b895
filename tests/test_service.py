"""Wire-true coordinator service (ISSUE 8): bit-exact serde round-trips
for every built-in codec's WireMsg, loopback-HTTP sync parity vs the
scan engine (K real client threads, measured bytes-on-wire ==
WireMsg.bits/8), the measured downlink CommRecord, and async
staleness-weighted rounds (scripted golden + e2e straggler run)."""
import dataclasses
import json
import os
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import example, given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    # hypothesis is a pinned requirement (requirements.txt) and the
    # serde property test is tier-1 in CI: REPRO_REQUIRE_HYPOTHESIS=1
    # there makes a missing install a hard failure instead of a skip.
    if os.environ.get("REPRO_REQUIRE_HYPOTHESIS", "") not in ("", "0"):
        raise
    HAVE_HYPOTHESIS = False

from repro.core import tree_num_params
from repro.data import (make_federated_dataset, make_image_task,
                        make_partition)
from repro.fed import (Experiment, ExperimentSpec, FLConfig, ServiceConfig,
                       WireMsg, algorithm_codec)
from repro.fed.service import serde
from repro.fed.service.runner import ServiceRunner
from repro.fed.service.server import Coordinator
from repro.models.cnn import mlp_apply, mlp_init, mlp_loss

KEY = jax.random.key(0)

# leaf sizes deliberately %32 != 0 so packed mask/quant words carry
# partial tails (the regression surface for framing/round-trip bugs)
TREE = {"w": jnp.zeros((33, 9)), "b": jnp.zeros((5,)),
        "deep": {"c": jnp.zeros((40, 7))}}
P = tree_num_params(TREE)

GOLDEN_STALENESS = os.path.join(os.path.dirname(__file__), "golden",
                                "service_staleness.json")


def _setup(algorithm="fedmrn", rounds=3, **cfg_kw):
    task = make_image_task(0, n=400, hw=8, n_classes=4, noise=0.5)
    parts = make_partition("iid", 0, task.y, 8)
    params = mlp_init(KEY, d_in=64, d_hidden=32, n_classes=4)
    cfg = FLConfig(algorithm=algorithm, num_clients=8, clients_per_round=4,
                   rounds=rounds, local_steps=2, batch_size=16, lr=0.1,
                   noise_alpha=3e-2, **cfg_kw)
    ds = make_federated_dataset(task.x, task.y, parts, batch_seed=7,
                                x_test=task.x[:128], y_test=task.y[:128])
    return mlp_loss, params, ds, cfg


def _experiment(algorithm="fedmrn", rounds=3, **cfg_kw):
    loss_fn, params, ds, cfg = _setup(algorithm, rounds, **cfg_kw)
    return Experiment(ExperimentSpec(loss_fn=loss_fn, params=params,
                                     data=ds, config=cfg,
                                     eval_apply=mlp_apply))


def _tree_bitwise_equal(a, b):
    jax.tree_util.tree_map(
        lambda x, y: np.testing.assert_array_equal(np.asarray(x),
                                                   np.asarray(y)), a, b)


# ---------------------------------------------------------------------------
# serde: deterministic frames, bit-exact round-trips (satellite)
# ---------------------------------------------------------------------------

def _codec_msg(algorithm, **cfg_kw):
    """A REAL encoded message of the registered algorithm's codec."""
    cfg = FLConfig(algorithm=algorithm, **cfg_kw)
    codec = algorithm_codec(cfg, TREE)
    payload = dict(codec.template_payload(TREE))
    # PRNG-key leaves can't ride a tree_map over ShapeDtypeStructs
    keyish = [k for k in ("seed", "key") if k in payload]
    for k in keyish:
        payload.pop(k)
    vals = jax.tree_util.tree_map(
        lambda s: jax.random.normal(KEY, s.shape, jnp.float32), payload)
    if "mask" in vals:
        vals["mask"] = jax.tree_util.tree_map(
            lambda l: jax.random.bernoulli(
                KEY, 0.5, jnp.shape(l)).astype(jnp.float32),
            vals["mask"])
    if "seed" in keyish:
        # the 64-bit (shared-)noise seed rides the wire as key_data
        vals["seed"] = jax.random.key(42)
    if "key" in keyish:
        vals["key"] = jax.random.key(7)
    return codec, codec.encode(vals)


CODEC_CASES = [
    ("fedmrn", {}),                          # MaskCodec + per-client seed
    ("fedmrn", {"shared_noise": True}),      # MaskCodec + shared seed
    ("fedmrns", {}),                         # signed masks
    ("fedpm", {}),                           # seedless binary masks
    ("signsgd", {}),                         # SignCodec words + scales
    ("fedavg", {}),                          # DenseCodec f32
    ("topk", {"topk_frac": 0.25}),           # SparseCodec idx + values
    ("qsgd", {"qsgd_bits": 2}),              # QuantCodec, fields %32 != 0
    ("terngrad", {}),                        # QuantCodec log2(3) fields
]


@pytest.mark.parametrize("algorithm, cfg_kw", CODEC_CASES,
                         ids=[f"{a}{'+shared' if k.get('shared_noise') else ''}"
                              for a, k in CODEC_CASES])
def test_serde_roundtrip_bit_exact_per_codec(algorithm, cfg_kw):
    """dumps_msg → loads_msg is bit-exact for every built-in codec's
    encoded WireMsg, and the framed payload equals msg.bits/8 with the
    framing overhead accounted separately."""
    codec, msg = _codec_msg(algorithm, **cfg_kw)
    blob = serde.dumps_msg(msg, round=3, cid=5, weight=1.0, loss=0.25)
    back, meta = serde.loads_msg(blob)
    assert back.codec == msg.codec
    assert sorted(back.buffers) == sorted(msg.buffers)
    _tree_bitwise_equal(back.buffers, msg.buffers)
    assert (meta["round"], meta["cid"]) == (3, 5)
    # measured bytes-on-wire == the codec's claimed wire size
    assert serde.payload_bits(msg.buffers) == msg.bits
    assert len(blob) * 8 == msg.bits + serde.framing_bits(blob, msg.buffers)
    # determinism: same message -> byte-identical frame
    assert serde.dumps_msg(msg, round=3, cid=5, weight=1.0,
                           loss=0.25) == blob


def test_serde_tree_roundtrip_and_template_mismatch():
    params = mlp_init(KEY, d_in=64, d_hidden=32, n_classes=4)
    state = {"scores": jax.tree_util.tree_map(jnp.ones_like, params)}
    blob = serde.dumps_tree({"params": params, "state": state},
                            round=0, done=False)
    tree, meta = serde.loads_tree(
        blob, {"params": params, "state": state})
    _tree_bitwise_equal(tree["params"], params)
    _tree_bitwise_equal(tree["state"], state)
    assert meta == {"round": 0, "done": False}
    with pytest.raises(ValueError, match="mismatch"):
        serde.loads_tree(blob, {"params": params, "state": {}})
    # PRNG key leaves must be framed as key_data, never raw
    with pytest.raises(TypeError, match="key"):
        serde.dumps_tree({"k": jax.random.key(0)})


def test_serde_rejects_corrupt_frames():
    _, msg = _codec_msg("fedmrn")
    blob = serde.dumps_msg(msg, round=0, cid=0)
    with pytest.raises(ValueError, match="magic"):
        serde.unpack_frame(b"XXXX" + blob[4:])
    with pytest.raises(ValueError, match="truncated"):
        serde.unpack_frame(blob[:-3])
    with pytest.raises(ValueError, match="trailing"):
        serde.unpack_frame(blob + b"\x00")


if HAVE_HYPOTHESIS:
    _DTYPES = st.sampled_from(["<f4", "<f8", "<i4", "<i8", "<u4", "<i1",
                               "<u1", "<i2"])
    _SHAPES = st.lists(st.integers(0, 7), min_size=0, max_size=3)

    @st.composite
    def _frames(draw):
        n = draw(st.integers(0, 4))
        bufs = {}
        for i in range(n):
            name = draw(st.text(
                alphabet=st.characters(min_codepoint=33, max_codepoint=126),
                min_size=1, max_size=12)) + f"#{i}"
            dtype = np.dtype(draw(_DTYPES))
            shape = tuple(draw(_SHAPES))
            size = int(np.prod(shape, dtype=np.int64))
            raw = draw(st.binary(min_size=size * dtype.itemsize,
                                 max_size=size * dtype.itemsize))
            bufs[name] = np.frombuffer(raw, dtype=dtype).reshape(shape)
        meta = {"round": draw(st.integers(0, 2 ** 31 - 1)),
                "tag": draw(st.text(max_size=8))}
        return meta, bufs

    @settings(max_examples=50, deadline=None)
    @given(_frames())
    @example(({"round": 0, "tag": ""}, {"!#0": np.zeros((), "<f4")}))
    def test_serde_frame_roundtrip_property(frame):
        """Any dict of arrays (incl. 0-size, 0-dim, sub-word dtypes and
        arbitrary byte patterns — NaN payloads too) survives
        pack→unpack bit-exactly."""
        meta, bufs = frame
        blob = serde.pack_frame(meta, bufs)
        meta2, bufs2 = serde.unpack_frame(blob)
        assert meta2 == meta
        assert sorted(bufs2) == sorted(bufs)
        for k in bufs:
            assert bufs2[k].dtype == bufs[k].dtype
            assert bufs2[k].shape == bufs[k].shape
            assert bufs2[k].tobytes() == bufs[k].tobytes()


# ---------------------------------------------------------------------------
# sync parity: K clients over loopback HTTP == the scan engine
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("algorithm, cfg_kw", [
    ("fedmrn", {}),
    ("fedmrn", {"shared_noise": True}),
    ("fedmrns", {}),
    ("fedpm", {}),
    ("qsgd", {"qsgd_bits": 2}),
    ("signsgd", {}),
], ids=["fedmrn", "fedmrn+shared", "fedmrns", "fedpm", "qsgd", "signsgd"])
def test_service_sync_matches_scan(algorithm, cfg_kw):
    """The acceptance criterion: real bytes over a real socket, same
    trajectory to 1e-6, same MEASURED per-round wire bits."""
    exp = _experiment(algorithm, **cfg_kw)
    rs = exp.run(engine="scan")
    rv = exp.run(engine="service")
    assert rv.engine == "service"
    np.testing.assert_allclose(rv.acc, rs.acc, atol=1e-6)
    np.testing.assert_allclose(rv.local_loss, rs.local_loss, atol=1e-6)
    np.testing.assert_array_equal(rv.schedule, rs.schedule)
    np.testing.assert_allclose(rv.uplink_bits_round, rs.uplink_bits_round)
    rep = exp.service_report
    assert rep.mode == "sync"
    assert rep.n_uplinks == exp.cfg.rounds * exp.cfg.clients_per_round


def test_service_measured_uplink_bytes_equal_wiremsg_bits():
    """Every uplink byte that crossed the socket is accounted: payload
    == n_uplinks x per-client WireMsg.bits (frame overhead separate)."""
    exp = _experiment("fedmrn", shared_noise=True)
    exp.run(engine="service")
    rep = exp.service_report
    codec = algorithm_codec(exp.cfg, exp.spec.params)
    per_client = codec.measured_bits(exp.spec.params)
    assert rep.uplink_payload_bits == rep.n_uplinks * per_client
    assert rep.uplink_framing_bits > 0      # framing is real, and small
    assert rep.uplink_framing_bits < rep.uplink_payload_bits


def test_service_downlink_bits_are_measured():
    """CommRecord.downlink_bits out of a service run is the MEASURED
    serialized params payload of GET /v1/model — and it equals the
    analytic 32P figure exactly, with frame + algorithm state reported
    separately as overhead."""
    exp = _experiment("fedmrn")
    exp.run(engine="service")
    rep = exp.service_report
    P_model = tree_num_params(exp.spec.params)
    assert rep.comm.downlink_bits == rep.downlink_params_bits
    assert rep.downlink_params_bits == 32 * P_model     # == analytic
    assert rep.downlink_total_bits > rep.downlink_params_bits
    assert (rep.downlink_overhead_bits
            == rep.downlink_total_bits - rep.downlink_params_bits)
    # every worker pulls the model once per round
    K, R = exp.cfg.clients_per_round, exp.cfg.rounds
    assert rep.downlink_requests >= K * R


def test_service_history_matches_schema_and_monitoring_endpoints():
    from urllib.request import urlopen
    exp = _experiment("fedmrn")
    hist = exp.run(engine="service").to_history()
    from repro.fed import HISTORY_KEYS
    assert set(hist) == set(HISTORY_KEYS)
    assert hist["engine"] == "service"
    # the coordinator is gone after the run — its port must be closed
    with pytest.raises(OSError):
        urlopen(exp.service_report.base_url + "/v1/status", timeout=0.5)


def test_service_rejects_bad_configs():
    exp = _experiment("fedmrn")
    with pytest.raises(ValueError, match="service="):
        exp.run(engine="scan", service=ServiceConfig())
    with pytest.raises(ValueError, match="sync"):
        exp.run(engine="service",
                service=ServiceConfig(straggler_slots=(0,)))
    with pytest.raises(ValueError, match="staleness_beta"):
        ServiceConfig(mode="async", staleness_beta=0.0).validate()
    with pytest.raises(ValueError, match="min_fresh"):
        exp.run(engine="service",
                service=ServiceConfig(mode="async", min_fresh=99))


# ---------------------------------------------------------------------------
# async rounds: staleness weighting (golden + e2e)
# ---------------------------------------------------------------------------

def _scripted_coordinator(beta=0.5, rounds=3, min_fresh=2):
    """A Coordinator driven directly (no HTTP, no threads): slot 2 of
    every round posts one round late — fully deterministic arrivals."""
    loss_fn, params, ds, cfg = _setup("fedmrn", rounds=rounds,
                                      shared_noise=True)
    runner = ServiceRunner(loss_fn, cfg, params, ds,
                           eval_program=None, eval_every=1)
    service = ServiceConfig(mode="async", staleness_beta=beta,
                            min_fresh=min_fresh, straggler_slots=(2,))
    from repro.fed.engine import make_client_schedule
    schedule = make_client_schedule(cfg, cfg.seed)
    coord = Coordinator(
        codec=runner.codec, partial_fn=runner._partial,
        merge_fn=runner._merge, finalize_fn=runner._finalize,
        apply_fn=runner._apply, eval_fn=None, eval_rounds=(),
        params=params, state=runner._state0, schedule=schedule,
        seed=cfg.seed, service=service, algorithm=cfg.algorithm)
    return runner, coord, schedule, cfg


def _post(runner, coord, r, slot, schedule):
    """Compute slot's uplink against the coordinator's CURRENT model and
    frame it exactly like the worker loop does."""
    cid = int(schedule[r][slot])
    msg, agg_w, loss = runner._client_step(
        jnp.int32(coord.seed), coord.w, coord.state, jnp.int32(r),
        jnp.int32(cid), jnp.float32(1.0))
    body = serde.dumps_msg(msg, round=r, cid=cid, weight=float(agg_w),
                           loss=float(loss))
    return coord.handle_uplink(r, body)


def test_async_staleness_weights_golden():
    """Scripted arrival order → the staleness log (who aggregated when,
    at which beta^lag scale) and per-round measured bits match the
    committed golden file byte for byte."""
    runner, coord, schedule, cfg = _scripted_coordinator()
    # round 0: slots 0,1 arrive -> closes at min_fresh=2 (slot 2 defers)
    deferred = []
    for r in range(cfg.rounds):
        for stale_r, stale_body in deferred:    # last round's straggler
            code, _ = coord.handle_uplink(stale_r, stale_body)
            assert code == 200
        deferred = []
        cid = int(schedule[r][2])
        msg, agg_w, loss = runner._client_step(
            jnp.int32(coord.seed), coord.w, coord.state, jnp.int32(r),
            jnp.int32(cid), jnp.float32(1.0))
        deferred.append((r, serde.dumps_msg(
            msg, round=r, cid=cid, weight=float(agg_w), loss=float(loss))))
        for slot in (0, 1):
            code, resp = _post(runner, coord, r, slot, schedule)
            assert code == 200
    assert coord.done
    got = {
        "beta": coord.service.staleness_beta,
        "schedule": schedule.tolist(),
        "staleness": coord.staleness_log,
        "uplink_bits_round": [float(b) for b in coord.uplink_bits],
        "n_uplinks": coord.n_uplinks,
    }
    with open(GOLDEN_STALENESS) as f:
        golden = json.load(f)
    assert got == golden, (
        "async staleness semantics drifted from "
        "tests/golden/service_staleness.json — if deliberate, regenerate "
        "the golden file (tests/test_service.py::_scripted_coordinator)")
    # invariants the golden encodes: stale entries carry beta^lag
    for r, row in enumerate(coord.staleness_log):
        for s in row:
            assert s["scale"] == coord.service.staleness_beta ** s["lag"]
            assert s["lag"] == r - s["round_sent"]


def test_async_sync_equivalence_when_nobody_is_late():
    """mode='async' with everyone on time IS the synchronous barrier:
    identical trajectory to the sync service (and hence to scan)."""
    exp = _experiment("fedmrn")
    rs = exp.run(engine="scan")
    rv = exp.run(engine="service", service=ServiceConfig(mode="async"))
    np.testing.assert_allclose(rv.acc, rs.acc, atol=1e-6)
    rep = exp.service_report
    assert all(s["lag"] == 0 and s["scale"] == 1.0
               for row in rep.staleness for s in row)


def test_async_straggler_e2e_converges_with_weighted_stale_uplinks():
    """The e2e acceptance: a real straggler thread over loopback HTTP.
    Thread timing makes WHICH round a stale message lands in
    nondeterministic, so assert the timing-independent invariants:
    every aggregated message's scale is exactly beta^lag, stale traffic
    exists, message conservation holds, and the run still converges."""
    exp = _experiment("fedmrn", rounds=4)
    beta = 0.5
    sc = ServiceConfig(mode="async", staleness_beta=beta,
                       straggler_slots=(3,))
    rs = exp.run(engine="scan")
    rv = exp.run(engine="service", service=sc)
    rep = exp.service_report
    K, R = exp.cfg.clients_per_round, exp.cfg.rounds
    entries = [s for row in rep.staleness for s in row]
    assert all(s["scale"] == beta ** s["lag"] for s in entries)
    assert any(s["lag"] > 0 for s in entries)       # stale traffic existed
    # conservation: every round's straggler message either lands one
    # round late or is dropped when the run finishes mid-defer
    assert R * K - R <= len(entries) <= R * K
    assert np.isfinite(rv.final_acc)
    # staleness-weighted rounds still learn (vs the initial accuracy)
    assert rv.final_acc >= rs.acc[0] - 0.05


def test_async_rejects_integer_count_aggregation():
    """count_dtype partials cannot carry beta^lag scales — refused at
    construction instead of silently dropping staleness weights."""
    loss_fn, params, ds, cfg = _setup("fedmrn", shared_noise=True)
    runner = ServiceRunner(loss_fn, cfg, params, ds)
    codec = dataclasses.replace(runner.codec, count_dtype=jnp.int8)
    with pytest.raises(ValueError, match="count_dtype"):
        Coordinator(
            codec=codec, partial_fn=runner._partial,
            merge_fn=runner._merge, finalize_fn=runner._finalize,
            apply_fn=runner._apply, params=params, state=runner._state0,
            schedule=np.zeros((2, 4), np.int32), seed=0,
            service=ServiceConfig(mode="async"), algorithm="fedmrn")
