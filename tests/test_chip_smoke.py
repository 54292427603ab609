"""chip_smoke.py must not be able to say "ok" without a chip, or after a
failed phase. (What it does ON the chip is the chip run's to show; these
are the two ways it could lie here.)"""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_chip_smoke_refuses_to_pass_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        capture_output=True, text=True, env=env, timeout=300, cwd=REPO,
    )
    assert r.returncode != 0
    assert '"ok": true' not in r.stdout
    assert "not a TPU" in r.stderr
    # it stopped at the device check: nothing was built or written
    assert "== 2 kernels" not in r.stdout


def test_chip_smoke_failed_phase_prints_no_result(monkeypatch, capsys,
                                                  tmp_path):
    """Kill a phase's assertion by hand: exit code 1, no result line —
    and the same run with the phase passing prints exactly one."""
    sys.path.insert(0, REPO)
    import chip_smoke

    import jax

    monkeypatch.setattr(chip_smoke, "phase_device", lambda n: jax.devices())
    monkeypatch.setattr(chip_smoke, "write_corpus", lambda d: None)
    monkeypatch.setattr(chip_smoke, "smoke_config", lambda *a: None)
    for name in ("phase_train", "phase_resume", "phase_serve",
                 "block_forward_vs_gather", "gated_delta_vs_numpy",
                 "latent_attention_vs_numpy"):
        monkeypatch.setattr(chip_smoke, name, lambda *a, **k: None)
    monkeypatch.setattr(
        sys, "argv", ["chip_smoke.py", "--workdir", str(tmp_path)]
    )

    def broken(seed):
        chip_smoke.check(False, "a kernel disagrees with its reference")

    monkeypatch.setattr(chip_smoke, "phase_kernels", broken)
    assert chip_smoke.main() == 1
    out = capsys.readouterr().out
    assert "CHECK FAILED: a kernel disagrees" in out
    assert "FAILED: 2 kernels: 1 check(s) failed" in out
    assert '"ok"' not in out

    monkeypatch.setattr(chip_smoke, "phase_kernels", lambda seed: None)
    assert chip_smoke.main() == 0
    last = capsys.readouterr().out.strip().splitlines()[-1]
    rec = json.loads(last)
    assert rec["ok"] is True
    assert set(rec["device"]) == {"platform", "kind", "count"}
    assert rec["device"]["count"] == len(jax.devices())
