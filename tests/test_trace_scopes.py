"""The round program names its phases and ``Experiment.run`` its host
phases: ``jax.named_scope`` puts ``fed.<phase>`` into the compiled
program's instruction metadata, and ``TraceAnnotation`` spans named
``fed.run.<what>`` bracket the host work of a run."""
import re

import jax
import jax.numpy as jnp
import pytest

from repro.data import make_federated_dataset, make_image_task, make_partition
from repro.fed import Experiment, ExperimentSpec, FLConfig, api
from repro.models.cnn import cnn_apply, cnn_init, cnn_loss

PHASES = {"gather", "noise", "local_train", "psm", "uplink", "server",
          "eval"}


def _experiment(algorithm):
    task = make_image_task(0, n=192, hw=8, n_classes=4)
    parts = make_partition("iid", 0, task.y, 6)
    ds = make_federated_dataset(task.x, task.y, parts, x_test=task.x[:48],
                                y_test=task.y[:48], batch_seed=1)
    params = cnn_init(jax.random.key(0), n_classes=4, channels=(4, 8), hw=8)
    cfg = FLConfig(algorithm=algorithm, num_clients=6, clients_per_round=2,
                   rounds=2, local_steps=2, batch_size=8)
    return Experiment(ExperimentSpec(
        loss_fn=cnn_loss, params=params, data=ds, config=cfg,
        eval_apply=cnn_apply, eval_batch_size=16))


def _innermost_scopes(exp) -> set:
    """The innermost ``fed.<phase>`` of every instruction's op_name in the
    compiled scan program."""
    run_chunk, state0, metrics0 = exp._program("seeded", exp.cfg)
    sched = jnp.zeros((exp.cfg.rounds, exp.cfg.clients_per_round),
                      jnp.int32)
    text = run_chunk.lower(jnp.int32(0), exp.spec.params, state0, metrics0,
                           jnp.int32(0), sched,
                           n_rounds=exp.cfg.rounds).compile().as_text()
    found = set()
    for op_name in re.findall(r'op_name="([^"]*)"', text):
        names = re.findall(r"(?<![\w.])fed\.(\w+)", op_name)
        if names:
            found.add(names[-1])
    return found


@pytest.fixture(scope="module")
def fedmrn_scopes():
    return _innermost_scopes(_experiment("fedmrn"))


def test_fedmrn_program_tags_every_phase(fedmrn_scopes):
    # fedmrn on the default config takes the fused uplink
    assert fedmrn_scopes == PHASES


def test_fedavg_program_has_no_noise_or_psm():
    assert _innermost_scopes(_experiment("fedavg")) == PHASES - {"noise",
                                                                 "psm"}


def test_run_opens_host_spans_in_order(monkeypatch):
    events = []

    class Recorder:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            events.append(("open", self.name))

        def __exit__(self, *exc):
            events.append(("close", self.name))

    exp = _experiment("fedavg")
    monkeypatch.setattr(api, "TraceAnnotation", Recorder)
    exp.run()
    names = ["fed.run.schedule", "fed.run.dispatch", "fed.run.readback",
             "fed.run.result"]
    assert events == [(e, n) for n in names for e in ("open", "close")]
