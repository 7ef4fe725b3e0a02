"""chip_smoke.py off the chip: what it must refuse, and a walk through
its control flow at tiny size.

None of this is a chip run. The rehearsal proves that the phases, their
checks and the last line are wired; only `chiprun -- python
chip_smoke.py` proves that the system starts on the chip.
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]


def run_smoke(args, cwd=REPO, script=REPO / "chip_smoke.py", timeout=900):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)   # one CPU device, as one chip
    return subprocess.run([sys.executable, str(script), *args], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=timeout)


def result_lines(stdout: str) -> list[str]:
    return [ln for ln in stdout.splitlines() if ln.startswith('{"ok"')]


@pytest.fixture(scope="module")
def rehearsal():
    return run_smoke(["--rehearse", "--seed", "3"])


class TestRehearsal:
    def test_passes_and_says_it_is_a_rehearsal(self, rehearsal):
        assert rehearsal.returncode == 0, rehearsal.stderr[-3000:]
        lines = rehearsal.stdout.splitlines()
        assert lines[0].startswith("REHEARSAL")
        assert "not a chip run" in lines[0]

    def test_last_line_has_the_contract_shape(self, rehearsal):
        last = json.loads(rehearsal.stdout.splitlines()[-1])
        assert last["ok"] is True
        assert set(last["device"]) == {"platform", "kind", "count"}
        assert last["device"]["count"] == 1
        assert result_lines(rehearsal.stdout) == \
            [rehearsal.stdout.splitlines()[-1]]

    def test_last_line_reports_the_true_platform(self, rehearsal):
        # the one thing that tells a rehearsal's last line from a chip's
        last = json.loads(rehearsal.stdout.splitlines()[-1])
        assert last["device"]["platform"] == "cpu"

    def test_every_phase_ran(self, rehearsal):
        out = rehearsal.stdout
        for mark in ("serve[gather]: 6 requests done",
                     "serve[pallas]: 6 requests done",
                     "pallas vs fp32 reference",
                     "step-0 loss jit",
                     "kernels: paged_attention decode"):
            assert mark in out, mark

    def test_leaves_only_ignored_files(self, rehearsal):
        st = subprocess.run(["git", "status", "--porcelain", "--", ".chip_smoke"],
                            cwd=REPO, capture_output=True, text=True)
        assert st.stdout.strip() == ""
        assert not list((REPO / ".chip_smoke").rglob("*.npz"))


class TestRefusals:
    def test_no_tpu_no_result(self):
        p = run_smoke([])
        assert p.returncode != 0
        assert "needs a TPU" in p.stderr
        assert result_lines(p.stdout) == []

    def test_four_chip_option_refuses_too(self):
        p = run_smoke(["--chips", "4"])
        assert p.returncode != 0
        assert result_lines(p.stdout) == []

    def test_script_alone_fails_without_the_program(self, tmp_path):
        alone = tmp_path / "chip_smoke.py"
        shutil.copy(REPO / "chip_smoke.py", alone)
        p = run_smoke(["--rehearse"], cwd=tmp_path, script=alone)
        assert p.returncode != 0
        assert result_lines(p.stdout) == []
        assert "hyperion_tpu" in p.stderr
