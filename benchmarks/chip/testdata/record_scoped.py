#!/usr/bin/env python3
"""Records ``scoped.xplane.pb`` on one chip: a scanned toy step whose
work sits in the program's device scopes (a gather, one differentiated
layer and head, an update), launched three times under the epoch
engine's host spans.  The device idles under ``stage_epoch`` before the
first launch and under no span finer than ``engine.run`` after each
loss fetch.  Prints the reduction of what it recorded.

    python3 benchmarks/chip/testdata/record_scoped.py <out.xplane.pb>
"""
import json
import os
import shutil
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
sys.path[:] = [os.path.join(ROOT, "src"), ROOT] + sys.path[1:]


def main(out: str) -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from benchmarks.chip import scopes
    from repro.trainer import tracing
    from repro.trainer.tracing import scope, span

    if jax.devices()[0].platform != "tpu":
        print("record_scoped.py needs a TPU chip", file=sys.stderr)
        return 1

    def step(w, table, idx):
        with scope("gather.features"):
            x = table[idx]

        def loss(w):
            with scope("gnn.layer0"):
                h = jnp.tanh(x @ w)
            with scope("head"):
                return jnp.mean(h * h)
        val, g = jax.value_and_grad(loss)(w)
        with scope("adamw"):
            w = w - 0.1 * g
        return w, val

    @jax.jit
    def epoch(w, table, blocks):
        return jax.lax.scan(lambda c, i: step(c, table, i), w, blocks)

    rng = np.random.default_rng(0)
    table = jnp.asarray(rng.standard_normal((8192, 512), np.float32))
    blocks_np = rng.integers(0, 8192, (4, 2048)).astype(np.int32)
    w = jnp.asarray(rng.standard_normal((512, 512), np.float32) * 0.04)
    w, losses = epoch(w, table, jnp.asarray(blocks_np))
    np.asarray(losses)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    d = tempfile.mkdtemp()
    jax.profiler.start_trace(d, profiler_options=opts)
    with span("engine.run"):
        with span("stage_epoch"):
            blocks = jax.device_put(blocks_np)
            time.sleep(0.02)
        for _ in range(3):
            with span("slice_chunk"):
                xs = blocks[:]
            with span("dispatch_epoch"):
                w, losses = epoch(w, table, xs)
            with span("fetch_losses"):
                np.asarray(losses)
            time.sleep(0.01)
    jax.profiler.stop_trace()
    shutil.copy(scopes.xplane_file(d), out)
    shutil.rmtree(d)
    rec = scopes.load(out, tracing.SPANS)
    print(json.dumps(scopes.reduce(rec, tracing.SCOPES, [0], 12)))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
