"""``trace_reduce.py`` on a trace worked by hand, and on a small recording
cut from a real v5e trace of the decode cell (``recorded/``)."""
import os

import pytest

from conftest import HERE

from benchmark import trace_reduce as tr

US = 1000  # ns


def hand_trace():
    """One chip, 1000 us window.  A while loop of 300 us holding two
    fusions (100 + 150 us), a gap of 200 us while the host sits in the
    program's server.py under the benchmark's await span, an all-reduce
    of 200 us whose second half overlaps a copy, then idle to the end."""
    ops = [
        ["%while.7 = (s32[], bf16[16,4096]) while((s32[]) %t), body=%b", 0, 300 * US, {}],
        ["%fusion.1 = bf16[16,4096]{1,0} fusion(bf16[16,4096]{1,0} %p), kind=kLoop", 10 * US, 100 * US, {}],
        ["%fusion.2 = bf16[16,4096]{1,0} fusion(bf16[16,4096]{1,0} %q), kind=kLoop", 120 * US, 150 * US, {}],
        # named by jax's psum; the opcode says what it is
        ["%psum.3 = f32[1024]{0:T(1024)} all-reduce(f32[1024]{0:T(1024)S(1)} %g), replica_groups={}", 500 * US, 200 * US, {}],
        ["%copy.4 = f32[1024]{0} copy(f32[1024]{0} %g)", 600 * US, 150 * US, {}],
        ["%flash.9 = bf16[128,1024,64]{2,1,0} custom-call(bf16[128,1024,64]{2,1,0} %a), "
         "custom_call_target=\"tpu_custom_call\"", 760 * US, 40 * US, {}],
    ]
    host = [
        ["bench.window", 0, 1000 * US, {}],
        ["bench.await_result", 5 * US, 990 * US, {}],
        ["$server.py:706 _run_group", 250 * US, 300 * US, {}],
        ["$queue.py:10 get", 300 * US, 100 * US, {}],       # not the program's file
        ["PjitFunction(generate)", 820 * US, 100 * US, {}],
    ]
    return [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": ops},
            {"name": "XLA Modules", "events": [
                ["jit__run(123)", 0, 300 * US, {}],
                ["jit__run(123)", 500 * US, 300 * US, {}]]}]},
        {"name": "/host:CPU", "lines": [{"name": "main", "events": host}]},
    ]


def test_hand_worked_reduction():
    r = tr.reduce(hand_trace(), program_files={"server.py"})
    assert r["chips"] == 1
    assert r["window_s"] == pytest.approx(1000e-6)
    # busy: [0,300] + [500,750] + [760,800] = 590 us — nested events once
    assert r["busy_s"] == pytest.approx(590e-6)
    # the all-reduce runs alone in [500,600]: 100 us exposed
    assert r["exposed_collective_s_by_chip"] == [pytest.approx(100e-6)]
    ops = {k.split(":")[0]: v for k, v in r["ops"].items()}
    assert ops["while.7"]["seconds"] == pytest.approx(50e-6)    # 300 - 250
    assert ops["fusion.2"]["seconds"] == pytest.approx(150e-6)
    assert ops["fusion.1"]["in_loop"] and ops["fusion.2"]["in_loop"]
    assert not ops["copy.4"]["in_loop"]
    assert ops["psum.3"]["seconds"] == pytest.approx(200e-6)  # overlap is not nesting
    assert r["loop_runs"] == 1
    assert r["modules"] == {"jit__run": {"seconds": pytest.approx(600e-6),
                                         "count": 2}}
    assert r["device_ops"][0][0].startswith("psum.3")
    assert [round(v * 1e6) for _, v in r["device_ops"][:3]] == [200, 150, 150]
    gaps = dict(r["idle_gaps"])
    # [300,500] under server.py's call; [800,1000] under the runtime span
    assert gaps["bench.await_result|server.py:706__run_group"] == pytest.approx(200e-6)
    assert gaps["bench.await_result|PjitFunction_generate_"] == pytest.approx(200e-6)
    assert gaps[tr.SMALL_GAPS] == pytest.approx(10e-6)           # [750,760]
    assert sum(gaps.values()) == pytest.approx(r["window_s"] - r["busy_s"])


def test_flash_rows_are_recognised():
    from benchmark.readers._common import is_flash, loop_seconds

    r = tr.reduce(hand_trace(), program_files=set())
    flash = [row for row in r["ops"].values() if is_flash(row, 64)]
    assert len(flash) == 1 and flash[0]["seconds"] == pytest.approx(40e-6)
    assert not [row for row in r["ops"].values() if is_flash(row, 128)]
    inside, outside = loop_seconds(r)
    assert inside == pytest.approx(300e-6)
    assert outside == pytest.approx(390e-6)


def test_no_chip_plane_is_an_error():
    with pytest.raises(ValueError):
        tr.reduce([{"name": "/host:CPU", "lines": []}])


RECORDED = os.path.join(HERE, "recorded", "v5e_decode_slice.json.gz")


@pytest.mark.skipif(not os.path.exists(RECORDED), reason="no recording")
def test_recorded_v5e_slice():
    """A 1.5 s cut of this PR's first traced run of the decode cell on
    the chip: the reduction finds the decode loop, the programs and a
    busy time inside the window, and every op second is booked once."""
    planes = tr.load_slice(RECORDED)
    r = tr.reduce(planes)
    assert r["chips"] == 1
    assert 0 < r["busy_s"] <= r["window_s"]
    assert r["loop_runs"] >= 1
    in_loop = sum(v["seconds"] for v in r["ops"].values() if v["in_loop"])
    total = sum(v["seconds"] for v in r["ops"].values())
    assert 0.5 * total < in_loop <= total        # decode dominates this cell
    assert total == pytest.approx(r["busy_s"], rel=0.02)
    assert any(k.startswith("jit") for k in r["modules"])
    assert r["device_ops"] == sorted(r["device_ops"], key=lambda kv: -kv[1])
