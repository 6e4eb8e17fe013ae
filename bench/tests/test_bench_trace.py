"""The reduction from a profiler trace to per-layer numbers."""
import json
from pathlib import Path

import pytest

from bench import trace as tr

DATA = Path(__file__).parent / "data"


def synthetic():
    """Two chips over a window of 1000 ns.  Chip 0: compute 0-100 and
    50-200, a collective 300-400 half hidden under compute 350-450, a
    kernel 600-700.  Chip 1: one op 0-500."""
    return {
        "device": {
            "/device:TPU:0": [
                ["fusion.1", "fusion", 0, 100], ["fusion.2", "fusion", 50, 150],
                ["all-gather.3", "all-gather", 300, 100],
                ["fusion.7", "fusion", 350, 100],
                ["kmeans_iter.4", "custom-call", 600, 100]],
            "/device:TPU:1": [["fusion.1", "fusion", 0, 500]],
        },
        "host": [["window", 0, 1000], ["stage2", 0, 480],
                 ["stage3", 500, 300]],
    }


def test_ops_named_from_their_hlo_text():
    text = ("%while.74 = (f32[101,14254]{1,0:T(8,128)}, s32[]{:T(128)}) "
            "while((f32[101,14254]{1,0:T(8,128)}, s32[]{:T(128)}) %tuple.3)"
            ", condition=%region_34, body=%region_17")
    assert tr.parse_op(text) == ("while.74", "while")
    assert tr.parse_op(
        "%knn_topk.1 = (f32[256,16]{1,0:T(8,128)S(1)}) custom-call(s32[1,1]"
        "{1,0:T(1,128)} %constant.52), custom_call_target=\"tpu_custom_call\""
    ) == ("knn_topk.1", "custom-call")
    assert tr.parse_op("fusion.12") == ("fusion.12", "fusion")
    assert tr.family("knn_topk.1") == "knn_topk"


def test_union_and_subtraction():
    assert tr.merge([(5, 7), (0, 2), (1, 3)]) == [(0, 3), (5, 7)]
    assert tr.subtract([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2), (3, 5)]
    assert tr.clip([(0, 4), (6, 9)], (2, 7)) == [(2, 4), (6, 7)]


def test_busy_union_and_idle_share():
    t = synthetic()
    # chip 0 busy: [0,200] + [300,450] + [600,700] = 450; chip 1: 500
    assert tr.busy_s(t, (0, 1000)) == pytest.approx(475e-9)
    assert tr.idle_share(t, (0, 1000)) == pytest.approx(52.5)


def test_kernel_time_and_calls_by_name():
    t = synthetic()
    assert tr.kernel_seconds(t, "kmeans_iter") == pytest.approx(100e-9)
    assert tr.kernel_seconds(t, "knn_topk") == 0
    assert tr.kernel_calls(t, "kmeans_iter", (0, 1000)) == 1
    assert tr.kernel_calls(t, "kmeans_iter", (0, 500)) == 0


def test_exposed_collective_time():
    # the all-gather 300-400 is covered by fusion.7 from 350: 50 ns exposed
    # on chip 0, none on chip 1 -> mean 25 ns
    assert tr.collective_exposed_s(synthetic(), (0, 1000)) == pytest.approx(25e-9)


def test_breakdown_names_ops_and_gaps_by_host_span():
    t = synthetic()
    ops = dict(tr.top_device_ops(t, (0, 1000), "between_jobs"))
    # fusion.1 ran on both chips inside stage2: (100 + 500) / 2 chips
    assert ops["stage2:fusion.1"] == pytest.approx(300e-9)
    assert ops["stage3:kmeans_iter.4"] == pytest.approx(50e-9)
    gaps = tr.longest_idle_gaps(t, (0, 1000), "between_jobs")
    # chip 0 idle: 700-1000 (300; middle 850, no stage open), 450-600
    # (150; middle 525 in stage3), 200-300 (100; middle 250 in stage2)
    assert gaps[0] == ["between_jobs", pytest.approx(300e-9)]
    assert gaps[1] == ["stage3", pytest.approx(150e-9)]
    assert gaps[2] == ["stage2", pytest.approx(100e-9)]


def test_breakdown_names_ops_by_their_program_first():
    """The device clock runs a few ms off the host's: an op of the next
    job's Stage 1 that the host clock puts inside ``stage3`` is named by
    the program it ran in; a program named after no span falls back to the
    host span."""
    t = synthetic()
    t["host"].append(["stage1", 800, 100])
    t["modules"] = {"/device:TPU:0": [["jit_stage2(3)", 0, 480],
                                      ["jit_stage1(1)", 590, 200]],
                    "/device:TPU:1": [["jit_serve_fn(2)", 0, 500]]}
    ops = dict(tr.top_device_ops(t, (0, 1000), "between_jobs"))
    assert ops["stage1:kmeans_iter.4"] == pytest.approx(50e-9)
    assert "stage3:kmeans_iter.4" not in ops
    # chip 1's program names no span: its op keeps the host span, stage2
    assert ops["stage2:fusion.1"] == pytest.approx(300e-9)
    gaps = tr.longest_idle_gaps(t, (0, 1000), "between_jobs")
    # 200-300 lies in stage2's run; 450-600 (middle 525) in no run, stage3
    assert gaps[1] == ["stage3", pytest.approx(150e-9)]
    assert gaps[2] == ["stage2", pytest.approx(100e-9)]


def test_recorded_chip_trace():
    """A trace recorded on a TPU v5e (one chip, the first job of a traced
    dti.job run, Stage 2 cut to its first and last 20 ms): the reduction
    finds the window, a busy share inside it, and the two Pallas kernels in
    their stages."""
    t = json.loads((DATA / "dti_job_trace_small.json").read_text())
    win = tr.span_windows(t, "window")[0]
    busy = tr.busy_s(t, win)
    assert 0 < busy <= (win[1] - win[0]) * 1e-9
    assert 0 < tr.idle_share(t, win) < 100
    assert tr.kernel_calls(t, "knn_topk", win) == 1
    assert tr.kernel_seconds(t, "knn_topk", win) > 0
    assert tr.kernel_seconds(t, "kmeans_iter", win) > 0
    names = [n for n, _ in tr.top_device_ops(t, win, "between_jobs")]
    assert names[0] == "stage1:knn_topk.1"
    assert {n.split(":")[0] for n in names} <= {"stage1", "stage2", "stage3"}
