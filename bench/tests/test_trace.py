"""The trace reduction, the peaks table and the FLOP and byte counts,
pinned on a small trace recorded on one TPU v5e by ``record_trace.py``
(twelve calls of a jitted ``_decode_fn`` inside ``bench.drive``, with
2 ms host sleeps in ``bench.pump`` between them)."""

from __future__ import annotations

import json

import pytest

from _tiny_cells import BENCH

from bench import common, peaks, trace

SMALL = str(BENCH / "data" / "small.xplane.pb")
DANUBE = json.loads((BENCH / "configs" / "h2o-danube-3-4b.json").read_text())["model"]


@pytest.fixture(scope="module")
def reduced():
    return trace.reduce(SMALL)


def test_window_busy_and_idle_are_pinned(reduced):
    assert reduced.window_s == pytest.approx(0.039743989, abs=1e-9)
    assert reduced.busy_s == {0: pytest.approx(0.002000066, abs=1e-9)}
    assert reduced.idle_share() == pytest.approx(0.9496762642521867, rel=1e-9)


def test_programs_are_found_by_their_jit_name(reduced):
    n, seconds = reduced.module_events("_decode_fn")
    assert n == 11
    assert seconds == pytest.approx(0.002000196, abs=1e-9)
    with pytest.raises(trace.TraceError):
        reduced.module_events("_prefill_fn")


def test_breakdown_names_ops_and_host_spans(reduced):
    names = [n for n, _ in reduced.device_ops]
    assert names[:2] == ["%convolution_tanh_fusion (fusion)", "%fusion (fusion)"]
    assert sum(s for _, s in reduced.device_ops) == pytest.approx(
        reduced.busy_s[0], rel=1e-3)
    gaps = dict(reduced.idle_gaps)
    assert set(gaps) == {"bench.pump"}
    assert gaps["bench.pump"] + reduced.busy_s[0] == pytest.approx(
        reduced.window_s, rel=1e-6)


@pytest.mark.parametrize("marked", [False, True])
def test_a_trace_without_mark_or_chip_is_an_error(tmp_path, marked):
    """A CPU trace has no TPU plane; an unmarked one has no window."""
    import contextlib

    import jax
    import jax.numpy as jnp

    tracer = trace.Tracer(str(tmp_path))
    if marked:
        tracer.start()
    else:
        jax.profiler.start_trace(str(tmp_path))
    with contextlib.suppress(Exception):
        jnp.ones(8).block_until_ready()
    if marked:
        tracer.stop()
    else:
        jax.profiler.stop_trace()
    with pytest.raises(trace.TraceError,
                       match="TPU" if marked else "bench.window"):
        trace.reduce(tracer.path())


def test_op_label():
    text = ("%while.12 = (s32[]{:T(128)}, bf16[1,1,3840]{2,1,0:T(2,128)(2,1)"
            "S(1)}) while((s32[]{:T(128)}, bf16")
    assert trace.op_label(text) == "%while.12 (while)"


def test_peaks_are_keyed_by_device_kind():
    assert peaks.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    assert peaks.peaks("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        peaks.peaks("cpu")


def test_danube_counts_from_shapes():
    # 24 x (3840*32*120*2 + 2*3840*8*120 + 3*3840*10240) + 32000*3840
    assert peaks.matmul_params(DANUBE) == 3_838_771_200
    assert peaks.weight_bytes(DANUBE) == 2 * 3_838_771_200 + 49 * 3840 * 4
    assert peaks.kv_bytes_per_position(DANUBE) == 92_160
    assert peaks.decode_flops(DANUBE, 0) == 2 * 3_838_771_200 + 24 * 4 * 32 * 120
    assert peaks.decode_bytes(DANUBE, 99) == peaks.weight_bytes(DANUBE) + 92_160 * 101
    one = peaks.prefill_flops(DANUBE, 1)
    assert one == 2.0 * 3_838_771_200 + 24 * 4 * 32 * 120
    assert peaks.prefill_flops(DANUBE, 256) < 256 * one


def test_quantile_spread():
    assert common.spread([1.0, 2.0, 3.0, 4.0, 5.0]) == pytest.approx(
        (4.5 - 1.5) / 3.0)
