"""Records the small chip trace of the program's spans that the span
readers' test reads.

    python3 benchmarks/chip/record_span_fixture.py

On a TPU, two rounds of: one call of a jitted 8192x8192 bf16 product
named ``decode_step`` inside a ``step.decode`` span, which then holds the
host for 3 ms more; two 512-MiB bf16 copies (``admit_copy``) enqueued back
to back inside a ``step.admit`` span that ends without waiting for them,
the wait after it; a 10 ms ``serve.pace`` sleep and a 20 ms ``serve.wait``
sleep.  One ``serve.start`` instant comes first.  Traced with the JAX
profiler as the harness traces a run; writes
``benchmarks/chip/fixtures/span_trace.xplane.pb``.
"""
import os
import shutil
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.join(HERE, "..", "..", "src")]
ROUNDS, N, COPY_SHAPE = 2, 8192, (16384, 16384)
HOST_S, PACE_S, WAIT_S = 0.003, 0.010, 0.020
OUT = os.path.join(HERE, "fixtures", "span_trace.xplane.pb")


def decode_step(x):
    import jax.numpy as jnp
    return jnp.tanh(x @ x)


def admit_copy(y):
    return y + 1


def main() -> int:
    import jax
    import jax.numpy as jnp

    import devtrace
    import hooks
    import spans

    if jax.devices()[0].platform != "tpu":
        print("record_span_fixture.py: needs a TPU", file=sys.stderr)
        return 1
    step, copy = jax.jit(decode_step), jax.jit(admit_copy)
    x = jnp.ones((N, N), jnp.bfloat16)
    y = jnp.ones(COPY_SHAPE, jnp.bfloat16)
    step(x).block_until_ready()
    copy(y).block_until_ready()
    ann = jax.profiler.TraceAnnotation
    tmp = tempfile.mkdtemp(dir=HERE)
    try:
        jax.profiler.start_trace(tmp,
                                 profiler_options=hooks.profile_options())
        with ann("serve.start"):
            pass
        for i in range(ROUNDS):
            with ann(spans.DECODE, iid=0, batch=1, ctx=N):
                step(x).block_until_ready()
                time.sleep(HOST_S)
            with ann(spans.ADMIT, iid=0, rid=i, slot=0):
                copies = [copy(y), copy(y)]
            jax.block_until_ready(copies)
            with ann(spans.PACE, iid=0, kind="decode"):
                time.sleep(PACE_S)
            with ann(spans.WAIT):
                time.sleep(WAIT_S)
        jax.profiler.stop_trace()
        shutil.copy(devtrace.find_xplane(tmp), OUT)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    ps = spans.read(OUT)
    print(f"fixture: {os.path.getsize(OUT)} bytes "
          f"window_s={ps.trace.window_s} busy_s={ps.trace.busy_s} "
          f"pace_idle_s={sum(ps.idle_inside(spans.PACE))} "
          f"decode_idle_s={ps.idle_inside(spans.DECODE)} "
          f"admit={[(sp, progs) for sp, progs in ps.launched(spans.ADMIT)]} "
          f"decode={[(sp, progs) for sp, progs in ps.launched(spans.DECODE)]}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
