"""Records the small chip trace the trace-reduction test reads.

    python3 benchmarks/chip/record_fixture.py

On a TPU: five calls of one jitted 8192x8192 bf16 product, each inside an
``engine.decode`` host span and followed by a 20 ms ``event_loop.sleep``
span, traced with the JAX profiler.  Writes
``benchmarks/chip/fixtures/small_trace.xplane.pb``.
"""
import os
import shutil
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.join(HERE, "..", "..", "src")]
CALLS, SLEEP_S, N = 5, 0.02, 8192


def fixture_step(x):
    import jax.numpy as jnp
    return jnp.tanh(x @ x)


def main() -> int:
    import jax
    import jax.numpy as jnp

    import devtrace
    import hooks

    if jax.devices()[0].platform != "tpu":
        print("record_fixture.py: needs a TPU", file=sys.stderr)
        return 1
    step = jax.jit(fixture_step)
    x = jnp.ones((N, N), jnp.bfloat16)
    step(x).block_until_ready()
    tmp = tempfile.mkdtemp(dir=HERE)
    try:
        jax.profiler.start_trace(tmp,
                                 profiler_options=hooks.profile_options())
        for _ in range(CALLS):
            with jax.profiler.TraceAnnotation(hooks.DECODE):
                step(x).block_until_ready()
            with jax.profiler.TraceAnnotation(hooks.SLEEP):
                time.sleep(SLEEP_S)
        jax.profiler.stop_trace()
        out = os.path.join(HERE, "fixtures", "small_trace.xplane.pb")
        shutil.copy(devtrace.find_xplane(tmp), out)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    t = devtrace.reduce(out, hooks.HOST_SPANS)
    print(f"fixture: {os.path.getsize(out)} bytes busy_s={t.busy_s} "
          f"window_s={t.window_s} programs={t.program_seconds()} "
          f"idle={t.idle_gaps()}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
