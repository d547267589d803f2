"""chip_smoke.py and the rules it proves on the chip, driven on the CPU.

The smoke's own run needs a TPU and is the driver's; here its leg
bodies run a 2,000-event day end to end with the kernels interpreted,
its failure paths are exercised, and the rules the bring-up PR fixed —
nothing re-points a process at the CPU behind the caller's back — are
pinned where tier-1 can see them.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

TINY = chip_smoke.SmokeSize(
    events=2000, n_src=60, n_dst=40, topics=4, batch=64, em_iters=4,
    serve_lines=512, vocab=256, bucket_len=16, repeat_tol=1e-3,
    device_score_min=64,
)


def _run(cmd, env, timeout=300):
    return subprocess.run(cmd, capture_output=True, text=True, env=env,
                          cwd=REPO, timeout=timeout)


def test_smoke_refuses_a_machine_without_the_chip():
    """Under JAX_PLATFORMS=cpu the smoke exits non-zero, names the
    platform it found, and prints no result line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = _run([sys.executable, os.path.join(REPO, "chip_smoke.py")], env)
    assert proc.returncode != 0
    assert "platform is 'cpu', not 'tpu'" in proc.stdout
    assert '"ok"' not in proc.stdout


def test_smoke_exit_is_nonzero_when_a_later_leg_fails(monkeypatch, capsys,
                                                      tmp_path):
    """Every leg's failure is the run's: a child that exits non-zero
    ends the parent non-zero with no result line, whichever leg it was;
    and a leg body that raises makes its child exit non-zero."""
    calls = []

    class FakeChild:
        pid = 0

        def __init__(self, cmd, **kw):
            self.leg = cmd[cmd.index("--leg") + 1]
            calls.append(self.leg)
            if self.leg != "serve":
                with open(os.path.join(cmd[cmd.index("--workdir") + 1],
                                       f"{self.leg}.json"), "w") as f:
                    json.dump({"device": {"platform": "tpu", "kind": "x",
                                          "count": 1}}, f)

        def wait(self, timeout=None):
            return 1 if self.leg == "serve" else 0

    monkeypatch.setattr(chip_smoke, "ROOT", str(tmp_path))
    monkeypatch.setattr(chip_smoke.subprocess, "Popen", FakeChild)
    monkeypatch.setattr(chip_smoke.os, "killpg", lambda *a: None)
    assert chip_smoke.main([]) == 1
    out = capsys.readouterr().out
    assert calls == ["kernels", "day", "serve"]   # day_repeat never ran
    assert "leg serve exited 1" in out and '"ok"' not in out

    def boom(*a, **kw):
        raise chip_smoke.SmokeFailure("planted")

    monkeypatch.setattr(chip_smoke, "run_leg", boom)
    args = chip_smoke.argparse.Namespace(
        leg="day", chips=1, workdir=str(tmp_path / "w"))
    assert chip_smoke._child_main(args) == 1
    assert "FAILED: planted" in capsys.readouterr().out


def test_kernels_leg_interpreted():
    """Every kernel the chip run compiles, interpreted at a tiny block
    shape, agrees with the XLA path at the f32 tolerances."""
    rec = chip_smoke.leg_kernels(TINY, interpret=True)
    assert set(rec["kernels"]) >= {
        "dense_rowmajor_f32", "dense_wmajor_bf16", "sparse_fused_f32",
        "sparse_fused_bf16", "pallas_fixed_point_f32",
        "shard_map_1x1_dense_wmajor_f32",
    }
    assert all(k["compiled"] and k["agrees_with_xla"]
               for k in rec["kernels"].values())
    assert rec["kernels"]["dense_wmajor_f32"]["tolerance"] == "f32"


def test_kernels_leg_fails_on_a_kernel_that_disagrees(monkeypatch):
    """A kernel that compiles but does not agree fails the leg, by
    name — it is not downgraded to a warning."""
    from oni_ml_tpu.ops import pallas_estep

    real = pallas_estep.e_step

    def skewed(*a, **kw):
        res = real(*a, **kw)
        return res._replace(likelihood=res.likelihood * 1.01)

    monkeypatch.setattr(pallas_estep, "e_step", skewed)
    with pytest.raises(chip_smoke.SmokeFailure,
                       match="pallas_fixed_point_f32.*likelihood"):
        chip_smoke.leg_kernels(TINY, interpret=True)


def test_day_serve_and_repeat_legs(tmp_path):
    """The smoke's day, serve, repeated-day and mesh legs on a
    2,000-event day: four stages through the CLI's main with everything
    that ran named, likelihoods finite and non-decreasing, results
    sorted, every serve future resolved with the device scorer
    exercised, the repeated day served by the compilation cache, and
    the --mesh 4,1 day sharded over four devices with the same
    likelihood."""
    work = str(tmp_path)
    day = chip_smoke.leg_day(work, TINY)
    assert day["events"] == 1999 and day["docs"] == 100
    assert day["featurizer"] == "native"
    assert day["estep_engine"] == {"value": "dense", "source": "default"}
    # Off the chip the Pallas gates refuse and the choice is reported.
    assert day["estep_kernel"]["value"] == "xla"
    assert day["estep_kernel"]["corpus_devices"] == [0]
    refused = day["estep_dispatch"][0]["refused"]
    assert set(refused) == {"sparse", "pallas"}
    assert "backend is cpu" in refused["sparse"]
    assert day["scorer"] == {"value": "host", "source": "default"}
    assert day["em_iters"] == len(day["likelihoods"]) == 4
    assert set(day["stages"]) == {"pre", "corpus", "lda", "score"}
    assert day["stages"]["lda"]["compile_requests"] > 0

    serve = chip_smoke.leg_serve(
        work, TINY, day_dir=os.path.join(work, "day", chip_smoke.FDATE))
    assert serve["lines"] == 512
    assert serve["calibration"]["source"] in ("measured", "plan")
    last = list(serve["runs"].values())[-1]
    assert last["device_batches"] >= 1
    assert last["stream_end"]["events_scored"] == 512

    # The smoke runs this leg in a new process, which holds no program
    # of the first day's and has only the compilation cache to find.
    import jax

    from oni_ml_tpu.models import fused

    fused.clear_programs()
    jax.clear_caches()
    repeat = chip_smoke.leg_day(work, TINY, name="day_repeat",
                                tol=TINY.repeat_tol)
    assert repeat["flagged"] > 0                  # the sort check read rows
    assert repeat["likelihoods"] == day["likelihoods"]
    chip_smoke._cross_checks(1, {"day": day, "day_repeat": repeat})

    # The four-chip mode's extra leg, on four of the virtual devices.
    mesh = chip_smoke.leg_day(work, TINY, name="day_mesh", mesh="4,1")
    assert mesh["estep_kernel"]["corpus_devices"] == [0, 1, 2, 3]
    chip_smoke._cross_checks(4, {"day": day, "day_mesh": mesh})


def test_mesh_cross_check_wants_four_devices_and_one_likelihood():
    """The four-chip cross-check: corpus shards on `chips` distinct
    devices and a final likelihood inside test_sharded's tolerance."""
    one = {"likelihoods": [-100.0, -90.0]}
    kernel = {"corpus_devices": [0, 1, 2, 3], "corpus_slices": 4}
    chip_smoke._cross_checks(4, {
        "day": one,
        "day_mesh": {"likelihoods": [-100.0, -90.000001],
                     "estep_kernel": kernel}})
    with pytest.raises(chip_smoke.SmokeFailure, match="corpus shards"):
        chip_smoke._cross_checks(4, {
            "day": one,
            "day_mesh": {"likelihoods": [-90.0], "estep_kernel": {
                "corpus_devices": [0, 0, 0, 0], "corpus_slices": 1}}})
    with pytest.raises(chip_smoke.SmokeFailure, match="final likelihood"):
        chip_smoke._cross_checks(4, {
            "day": one,
            "day_mesh": {"likelihoods": [-90.1], "estep_kernel": kernel}})


# ---------------------------------------------------------------------------
# Nothing re-points a process at the CPU
# ---------------------------------------------------------------------------


def _clean_env(**extra):
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PLATFORMS", "XLA_FLAGS")}
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env.update(extra)
    return env


def test_dryrun_with_too_few_devices_and_no_cpu_request_raises():
    """One device, and the caller did not ask for the CPU: the dry run
    fails and says what it needs — it does not build itself a virtual
    CPU mesh."""
    proc = _run([sys.executable, "-c",
                 "import __graft_entry__ as g; g.dryrun_multichip(8)"],
                _clean_env())
    assert proc.returncode != 0
    assert "needs 8 devices; jax has 1" in proc.stderr
    assert "JAX_PLATFORMS=cpu" in proc.stderr


def test_dryrun_provisions_the_cpu_mesh_when_asked():
    """JAX_PLATFORMS=cpu is the explicit request: the device count is
    raised to what the dry run needs."""
    proc = _run([sys.executable, "-c",
                 "import __graft_entry__ as g, jax; g._ensure_devices(8); "
                 "print(len(jax.devices()), jax.default_backend())"],
                _clean_env(JAX_PLATFORMS="cpu"))
    assert proc.returncode == 0, proc.stderr[-500:]
    assert proc.stdout.split() == ["8", "cpu"]
