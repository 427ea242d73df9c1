"""Record the small profiler trace that ``test_trace`` reads.

    python bench/tests/record_trace.py OUT_DIR

On a TPU: a 0.2 s window (the ``bench.window`` span) of a few jitted
matrix products with host pauses between them, traced by the session a
traced run uses (``bench.trace.start``).  Writes ``OUT_DIR/small.xplane.pb``;
copy it to ``bench/tests/data/``.
"""
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[0] = os.path.dirname(os.path.dirname(HERE))


def main(out_dir):
    import jax
    import jax.numpy as jnp

    from bench import run, trace
    from bench.meter import Spans

    if jax.devices()[0].platform != "tpu":
        print("record_trace: needs a TPU", file=sys.stderr)
        return run.NO_DEVICE
    f = jax.jit(lambda a, b: jnp.tanh(a @ b))
    a = jnp.ones((512, 512), jnp.float32)
    jax.block_until_ready(f(a, a))
    spans = Spans(annotate=True)
    session = trace.start()
    with spans.span("bench.window"):
        for _ in range(4):
            with spans.span("bench.pair_update"):
                jax.block_until_ready(f(a, a))
                time.sleep(0.05)
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "small.xplane.pb"), "wb") as fh:
        fh.write(session.stop())
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
