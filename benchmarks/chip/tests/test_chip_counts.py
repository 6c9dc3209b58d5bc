"""The yardstick's arithmetic: operation and byte counts worked by hand
for the configuration (its architecture module's counts), the peaks
table, and the trace reduction on a small trace recorded on a TPU v5e
(``fixtures/small_trace.xplane.pb``, made by ``record_fixture.py``)."""
import os

import pytest

import chip_tiny
import counts
import devtrace
import hooks
import peaks
import spec

FIXTURE = os.path.join(chip_tiny.CHIP, "fixtures", "small_trace.xplane.pb")


def _cfg(name):
    cell = chip_tiny.load(name)
    return spec.model_config(cell.config), cell.arch


def test_chatglm3_counts_by_hand():
    cfg, arch = _cfg("chatglm3-6b.sharegpt")
    # q 4096x4096, k and v 4096x256 each, o 4096x4096, gate/up/down
    # 3 x 4096x13696
    per_layer = (16_777_216 + 2 * 1_048_576 + 16_777_216 + 168_296_448)
    assert arch.layer_matmul_params(cfg) == per_layer == 203_948_032
    # + two norms (2 x 4096) + qkv bias (4096 + 2 x 256), 28 layers,
    # final norm 4096, head 4096 x 65,024; two bytes each
    assert arch.weight_bytes(cfg) == 2 * (
        28 * (203_948_032 + 8_192 + 4_608) + 4_096 + 266_338_304)
    assert arch.weight_bytes(cfg) == 11_954_491_392
    # 2 (k, v) x 28 layers x 2 heads x 128 x 2 bytes = 28 KiB
    assert arch.kv_bytes_per_token(cfg) == 28_672
    # 1,024-token prompt: 2 x 1024 x 28 x 203,948,032 matmul, causal
    # pairs 1024 x 1025 / 2 = 524,800 at 4 x 28 x 32 x 128 each, head on
    # one row 2 x 4096 x 65,024
    assert arch.prefill_flops(cfg, 1024) == (
        11_695_195_947_008 + 240_753_049_600 + 532_676_608)
    # decode, 3 slots with 100 + 200 + 300 context: 3 rows through the
    # layers and the head, 603 attention pairs
    assert arch.decode_flops(cfg, 3, 600) == (
        2 * 3 * (28 * 203_948_032 + 266_338_304)
        + 4 * 28 * 32 * 128 * 603)
    assert arch.decode_bytes(cfg, 3, 600) == (
        11_954_491_392 + 28_672 * 603 + 2 * 3 * 4096)


def test_roofline_takes_the_larger_bound():
    pk = peaks.peaks("TPU v5 lite")
    assert counts.roofline_s(197e12, 0, pk) == pytest.approx(1.0)
    assert counts.roofline_s(0, 819e9, pk) == pytest.approx(1.0)
    assert counts.roofline_s(197e12, 2 * 819e9, pk) == pytest.approx(2.0)
    with pytest.raises(KeyError):
        peaks.peaks("cpu")


def test_union_and_idle_gap_attribution():
    assert devtrace.union([(3, 4), (0, 1), (0.5, 2)]) == [(0, 2), (3, 4)]
    t = devtrace.DeviceTrace(
        window=(0.0, 10.0), busy={0: [(1.0, 2.0), (6.0, 7.0)]},
        modules=[(1.0, 2.0, "jit_a(1)"), (5.6, 5.7, "jit_pad(2)"),
                 (6.0, 7.0, "jit_step(3)")],
        host={hooks.SLEEP: [(2.0, 5.5)], hooks.DECODE: [(5.5, 7.0)]})
    assert t.busy_s == 2.0 and t.window_s == 10.0
    gaps = t.idle_gaps()
    # [0,1) overlaps nothing, [2,6) is mostly sleep, [7,10) nothing
    assert gaps == {"host.other": 4.0, hooks.SLEEP: 4.0}
    # every program inside a decode span counts as the step's device time
    assert t.span_seconds(hooks.DECODE) == pytest.approx(1.1)
    assert t.span_seconds(hooks.PREFILL) == 0
    assert t.program_seconds() == pytest.approx({
        "jit_a@host.other": 1.0, "jit_pad@engine.decode": 0.1,
        "jit_step@engine.decode": 1.0})


def test_prefill_mfu_reads_every_program_of_the_traced_prefills():
    """The reader charges the prefills the trace holds (its first calls)
    with the device time of every program inside their spans."""
    import runner
    from repro.serving.calibration import CalibrationRecorder

    c = spec.load_cell("chatglm3-6b.sharegpt")
    cfg = spec.model_config(c.config)
    t = devtrace.DeviceTrace(
        window=(0.0, 10.0), busy={0: [(1.0, 1.5), (3.0, 3.25)]},
        modules=[(1.0, 1.4, "jit__unknown(1)"), (1.4, 1.5, "jit_scatter(2)"),
                 (3.0, 3.25, "jit__unknown(1)")],
        host={hooks.PREFILL: [(0.9, 1.6), (2.9, 3.3)],
              hooks.SLEEP: [(1.6, 2.9)]})
    rec = runner.Records(
        cell=c, cfg=cfg, seconds=1.0, due=[], window_s=10.0,
        clock=hooks.BenchClock(), events=[], recorder=CalibrationRecorder(),
        trace=t, peak=peaks.peaks("TPU v5 lite"),
        prefills=[[1024, 512], [2048], [4096]])   # the last call untraced
    flops = sum(c.arch.prefill_flops(cfg, n) for n in (1024, 512, 2048))
    want = 100.0 * flops / 197e12 / 0.75
    assert runner.load_reader("prefill_mfu")(rec) == pytest.approx(want)


def test_profiler_stop_is_not_on_the_timeline():
    """The time the profiler takes to stop (to write its trace) is left
    out of the loop's timeline, and stopping happens once."""
    import time

    calls = []
    clock = hooks.BenchClock()
    clock.on_stop = lambda: (calls.append(1), time.sleep(0.3))
    clock.stop_at = 0.0
    clock.start()
    clock.sleep_until(0.01)         # wakes past stop_at: the stop runs
    clock.sleep_until(0.02)
    assert calls == [1]
    assert clock.now() < 0.2
    assert clock.wakes[0][1] >= 0.01


def test_recorded_chip_trace():
    """Five 8192^3 bf16 products (1.1 TFLOP each, about 5.6 ms at the
    197 TFLOP/s peak) with 20 ms host sleeps between them."""
    t = devtrace.reduce(FIXTURE, hooks.HOST_SPANS)
    assert list(t.busy) == [0]
    steps = t.by_span()[hooks.DECODE]
    assert len(steps) == 5
    assert all("fixture_step" in n for *_, n in steps)
    per_call = t.span_seconds(hooks.DECODE) / 5
    assert 2 * 8192 ** 3 / 197e12 <= per_call < 0.05
    assert 0 < t.busy_s < t.window_s
    gaps = t.idle_gaps()
    assert gaps[hooks.SLEEP] >= 4 * 0.02
    assert len(t.host[hooks.DECODE]) == 5 and len(t.host[hooks.SLEEP]) == 5
