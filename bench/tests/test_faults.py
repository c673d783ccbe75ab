"""The harness, driven on the CPU with its look for a chip skipped,
says ``correct: false`` when the timed path is broken underneath: a
device answer altered where it is produced, the device program run in
float32 where it states float64, a trace answer altered where it is
produced, and half of the groups left out."""
import numpy as np
import pytest

from conftest import result_line


def drive(run, root, cell, capsys, seed=9007199254740993):
    rc = run.main(["--workload", cell, "--seed", str(seed),
                   "--seconds", "0.01", "--trace", "0"], root=root)
    assert rc == 0
    return result_line(capsys)


@pytest.mark.parametrize("cell", ["tiny.qps", "tiny.plane"])
def test_sound_run_is_correct(run_on_cpu, tiny_root, capsys, cell):
    res = drive(run_on_cpu, tiny_root, cell, capsys)
    assert res["correct"] is True, res["check"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert list(res)[-1] == "check"
    assert res["metrics"]["scenarios_per_s"]["value"] > 0


@pytest.mark.parametrize("cell", ["tiny.qps", "tiny.plane"])
def test_device_answer_altered(run_on_cpu, tiny_root, capsys, monkeypatch,
                               cell):
    from repro.sweep import device
    real = device._program

    def altered():
        prog = real()

        def call(*args):
            out = list(prog(*args))
            out[0] = out[0] * (1.0 + 1e-3)      # summed P*dt, 0.1% off
            return tuple(out)
        return call
    monkeypatch.setattr(device, "_program", altered)
    res = drive(run_on_cpu, tiny_root, cell, capsys)
    assert res["correct"] is False
    assert res["check"]["power_rel"]["value"] > \
        res["check"]["power_rel"]["limit"]


@pytest.mark.parametrize("cell", ["tiny.qps", "tiny.plane"])
def test_device_program_in_float32(run_on_cpu, tiny_root, capsys,
                                   monkeypatch, cell):
    from repro.sweep import device
    real = device._program

    def single():
        prog = real()

        def call(*args):                    # roofline and sums in f32
            low = [a.astype(np.float32) if a.dtype == np.float64 else a
                   for a in args]
            return tuple(np.asarray(o, np.float64) for o in prog(*low))
        return call
    monkeypatch.setattr(device, "_program", single)
    res = drive(run_on_cpu, tiny_root, cell, capsys)
    assert res["correct"] is False
    assert res["check"]["duration_rel"]["value"] > \
        res["check"]["duration_rel"]["limit"]


@pytest.mark.parametrize("cell", ["tiny.qps", "tiny.plane"])
def test_trace_answer_altered(run_on_cpu, tiny_root, capsys, monkeypatch,
                              cell):
    from repro.sweep import device, divergence
    import repro.sim as sim

    def late(fn):
        def wrapped(cfg, *a, **kw):
            res = fn(cfg, *a, **kw)
            for r in res.requests:          # every completion 1 ppm late
                r.t_done = r.t_done * (1 + 1e-6)
            return res
        return wrapped
    monkeypatch.setattr(sim, "run_simulation", late(sim.run_simulation))
    monkeypatch.setattr(divergence, "replay_result",
                        late(divergence.replay_result))
    res = drive(run_on_cpu, tiny_root, cell, capsys)
    assert res["correct"] is False
    assert res["check"]["trace_rel"]["value"] > \
        res["check"]["trace_rel"]["limit"]


@pytest.mark.parametrize("cell", ["tiny.qps", "tiny.plane"])
def test_half_the_groups_left_out(run_on_cpu, tiny_root, capsys,
                                  monkeypatch, cell):
    from repro.sweep import device
    real = device.execute_device_grid

    def half(scenarios, *a, **kw):
        recs, stats = real(scenarios, *a, **kw)
        keys = list(dict.fromkeys(sc.trace_key for sc in scenarios))
        keep = set(keys[::2])
        by_key = {sc.key: sc.trace_key for sc in scenarios}
        return [r for r in recs if by_key[r["key"]] in keep], stats
    monkeypatch.setattr(device, "execute_device_grid", half)
    res = drive(run_on_cpu, tiny_root, cell, capsys)
    assert res["correct"] is False
    assert res["check"]["assembly_faults"]["value"] > 0
    assert res["failed"] > 0
