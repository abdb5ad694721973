"""``chip_smoke.py`` on the CPU: the legs at tiny sizes, and the rules
by which the script fails. What only the chip can show — that the same
legs run there at 1008 and 10k nodes — is the script's own job."""

import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import chip_smoke  # noqa: E402
from openr_tpu.decision import spf_solver  # noqa: E402
from openr_tpu.telemetry import get_registry  # noqa: E402

DEVICE = {"platform": "cpu", "kind": "cpu", "count": 8}


@pytest.fixture
def artefacts(tmp_path, monkeypatch):
    monkeypatch.setattr(chip_smoke, "ARTEFACT_DIR", str(tmp_path / "out"))
    return tmp_path / "out"


def test_refuses_to_run_without_a_tpu(capsys):
    assert chip_smoke.main() == 2
    out, err = capsys.readouterr()
    assert out == ""  # no result of any kind on stdout
    assert "no TPU" in err


def test_every_leg_passes_at_tiny_sizes(artefacts, monkeypatch, capsys):
    def sparse_pipeline():
        # 64 nodes on the resident sliced-ELL formulation: the path the
        # 10k leg takes on the chip
        with monkeypatch.context() as patch:
            patch.setattr(spf_solver, "SPARSE_NODE_THRESHOLD", 32)
            return chip_smoke.leg_pipeline(64, events=10, rate=20)

    legs = [
        ("pipeline_dense",
         lambda: chip_smoke.leg_pipeline(64, events=6, rate=20)),
        ("pipeline_sparse", sparse_pipeline),
        ("ksp2", lambda: chip_smoke.leg_ksp2(64, events=2)),
        # 100 nodes, 18 hops from the corner: past the old hop gate
        ("ksp2_grid", lambda: chip_smoke.leg_ksp2_grid(10, events=2)),
        # two areas of a 22-node fabric joined by two borders
        ("multiarea", lambda: chip_smoke.leg_multiarea(
            topology={"pods": 3, "ssw_per_plane": 2, "fsw_per_pod": 2,
                      "rsw_per_pod": 4}, timeout_s=60.0,
        )),
        # 12 rounds, not 3: at these sizes a client process is done in
        # well under the time the other takes to spawn on a loaded
        # machine (six xdist workers), and then no request ever arrives
        # during a wave (`tenancy.wave_joins never ran`)
        ("serve", lambda: chip_smoke.leg_serve(
            tenant_sizes=(("grid", 4), ("mesh", 20)), rounds=12,
        )),
        ("mesh4", lambda: chip_smoke.leg_mesh4(
            nodes_ksp2=64, nodes_engine=64, events=1,
        )),
    ]
    rc = chip_smoke.run(legs, DEVICE, {"versions": chip_smoke._versions()})
    out, err = capsys.readouterr()
    assert rc == 0, err
    lines = out.strip().splitlines()
    # the last line is the verdict, with exactly these keys
    assert json.loads(lines[-1]) == {"ok": True, "device": DEVICE}
    assert lines[-2].startswith("summary: ")
    summary = json.loads(lines[-2][len("summary: "):])
    assert summary["ok"] is True
    assert summary["device"] == DEVICE
    assert list(summary)[-1] == "claim" and summary["claim"] is None
    assert set(summary["legs"]) == {name for name, _ in legs}
    assert not any(summary["fallback_counters"].values())
    assert all(summary["mechanism_counters"].values())
    assert summary["legs"]["ksp2_grid"]["hops_from_root"] == 18
    multiarea = summary["legs"]["multiarea"]
    assert (multiarea["nodes_per_area"], multiarea["routes"],
            multiarea["reoriginated_keys"]) == (22, 41, 41)
    for leg in ("pipeline_dense", "pipeline_sparse", "ksp2", "ksp2_grid",
                "multiarea", "serve"):
        assert summary["legs"][leg]["parity"] is True
    assert summary["legs"]["mesh4"]["shard_devices"] == [0, 1, 2, 3]
    assert summary["compile"]["compiles"] >= 0
    with open(artefacts / "chip_smoke.json") as f:
        assert json.load(f) == summary


def test_a_raising_leg_is_not_caught(artefacts):
    def boom():
        raise chip_smoke.SmokeFailure("parity miss")

    with pytest.raises(chip_smoke.SmokeFailure):
        chip_smoke.run([("boom", boom)], DEVICE, {})
    assert not (artefacts / "chip_smoke.json").exists()


def test_a_fallback_counter_fails_the_run(artefacts, capsys):
    reg = get_registry()
    prev = reg.counter_get("ops.aot_fallbacks")

    def quiet_retry():
        reg.counter_bump("ops.aot_fallbacks")
        return {}

    try:
        assert chip_smoke.run([("leg", quiet_retry)], DEVICE, {}) == 1
    finally:
        # the registry is process-wide and other tests assert on it
        reg.counter_set("ops.aot_fallbacks", prev)
    out, err = capsys.readouterr()
    assert "ops.aot_fallbacks = 1" in err
    # no result line: only the progress lines reach stdout
    assert not out.strip().splitlines()[-1].startswith("{")
    with open(artefacts / "chip_smoke.json") as f:
        assert json.load(f)["ok"] is False


def test_a_mechanism_that_never_ran_fails_the_run(artefacts, capsys):
    assert chip_smoke.run([("noop", dict)], DEVICE, {}) == 1
    _out, err = capsys.readouterr()
    assert "decision.ell_warm_solves never ran" in err
    assert "tenancy.wave_joins never ran" in err
