"""Record ``phases.xplane.pb`` and ``phases.hlo.txt`` on a TPU, the
trace that ``tests/test_bench_phases.py`` reads.

    python3 bench/testdata/record_phases.py [--out <dir>]

(``--out`` defaults to this directory).  The trace then names this
script by its absolute path in its source locations; the committed
fixture has those paths made relative to the checkout.

The program is a jitted ``lax.scan`` of three steps.  Its body takes a
gradient step under ``fed.local_train``, whose loss holds ``fed.psm``
(so the backward pass carries ``transpose(jvp(fed.psm))``), sums the
weights under ``fed.uplink``, and updates the input with no scope.  On
the TPU, XLA fuses the ``fed.psm`` and ``fed.uplink`` instructions into
fusions named by ``fed.local_train`` or by no scope; they stay in the
HLO text, inside the fused computations.  On the host, three iterations
of: span ``bench.call`` holding 2 ms of sleep, then ``fed.run.dispatch``
(2 ms of sleep and the call) and ``fed.run.readback`` (the read of the
result); then 2 ms of sleep outside any span.
"""
import argparse
import glob
import os
import shutil
import time

import jax
import jax.numpy as jnp
import numpy as np


def loss(w, x):
    with jax.named_scope("fed.psm"):
        w_hat = w + 0.1 * jnp.tanh(w)
    return jnp.mean((x @ w_hat) ** 2)


def body(carry, _):
    w, x = carry
    with jax.named_scope("fed.local_train"):
        value, g = jax.value_and_grad(loss)(w, x)
        w = w - 0.1 * g
    with jax.named_scope("fed.uplink"):
        s = jnp.sum(w, axis=0)
    x = x * 0.5 + s[None, :] * 1e-3
    return (w, x), value


@jax.jit
def program(w, x):
    (w, x), values = jax.lax.scan(body, (w, x), None, length=3)
    return w, x, values


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    here = os.path.dirname(os.path.abspath(__file__))
    ap.add_argument("--out", default=here)
    out = ap.parse_args().out
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("needs a TPU")
    os.makedirs(out, exist_ok=True)
    w = jnp.eye(128, dtype=jnp.float32)
    x = jnp.ones((128, 128), jnp.float32)
    with open(os.path.join(out, "phases.hlo.txt"), "w") as f:
        f.write(program.lower(w, x).compile().as_text())
    np.asarray(program(w, x)[2])                      # compile outside
    tdir = os.path.join(out, "trace")
    shutil.rmtree(tdir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(tdir, profiler_options=opts):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.call"):
                time.sleep(0.002)
                with jax.profiler.TraceAnnotation("fed.run.dispatch"):
                    time.sleep(0.002)
                    res = program(w, x)
                with jax.profiler.TraceAnnotation("fed.run.readback"):
                    np.asarray(res[2])
            time.sleep(0.002)
    path, = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"),
                      recursive=True)
    shutil.copy(path, os.path.join(out, "phases.xplane.pb"))
    shutil.rmtree(tdir)
    print(os.path.getsize(os.path.join(out, "phases.xplane.pb")))


if __name__ == "__main__":
    main()
