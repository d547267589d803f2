"""Fast serving sanity check: run `ml_ops serve --dry-run` (single
model) AND `ml_ops serve --dry-run --fleet synthetic` (2-tenant fleet)
in clean subprocesses, on the jax platform named on the command line,
and verify both summary lines.

The single dry run exercises the whole serving stack — registry
publish, micro-batch flush triggers, host scoring, mid-stream
online-LDA refresh hot-swap, per-batch metrics.  The fleet dry run
exercises the multi-tenant path end to end — FleetRegistry stacked
snapshots, cross-tenant packed flushes, per-tenant demux, and
hot-swap isolation (tenant 0 republish leaves tenant 1's versions and
futures untouched).  Both run against synthetic in-memory days, so
this is the one-command check that the streaming paths still work on a
box with no day data.  tests/test_serving.py / tests/test_fleet.py
carry the same paths as tier-1 tests; this wrapper is the operator/CI
front door:

    python tools/serve_smoke.py cpu     # or: tpu, on a machine with a free chip
"""

import json
import os
import subprocess
import sys

MODES = {
    "single": ["serve", "--dry-run"],
    "fleet": ["serve", "--dry-run", "--fleet", "synthetic"],
}
_OK_KEYS = {"single": "serve_dry_run", "fleet": "serve_fleet_dry_run"}


def run_smoke(mode: str = "single", timeout_s: float = 300.0, *,
              platform: str) -> dict:
    """One dry run in a child process on the jax platform the caller
    names (nothing here picks one behind the caller's back)."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = platform
    proc = subprocess.run(
        [sys.executable, "-m", "oni_ml_tpu.runner.ml_ops",
         *MODES[mode]],
        capture_output=True, text=True, timeout=timeout_s, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    lines = [ln for ln in proc.stdout.strip().splitlines() if ln]
    summary = None
    if lines:
        try:
            summary = json.loads(lines[-1])
        except ValueError:
            pass
    return {
        "rc": proc.returncode,
        "summary": summary,
        "stderr_tail": proc.stderr.strip()[-500:],
    }


def main() -> int:
    if len(sys.argv) != 2:
        print("usage: serve_smoke.py PLATFORM   (cpu | tpu)",
              file=sys.stderr)
        return 2
    out = {}
    ok = True
    for mode in MODES:
        res = run_smoke(mode, platform=sys.argv[1])
        mode_ok = (
            res["rc"] == 0
            and isinstance(res["summary"], dict)
            and res["summary"].get(_OK_KEYS[mode]) == "ok"
        )
        ok = ok and mode_ok
        out[mode] = {"ok": mode_ok, **res}
    print(json.dumps({"serve_smoke": "ok" if ok else "FAILED",
                      "modes": out}))
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
