"""chip_smoke.py at tiny size on the host: the script refuses a run with
no TPU, and every phase passes on CPU (the four-chip phase on 4 of the 8
virtual devices). The real sizes run on the chip only."""

import os
import subprocess
import sys

import jax
import pytest

import chip_smoke

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_chip_smoke_fails_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        capture_output=True, text=True, cwd=REPO, env=env, timeout=300,
    )
    assert r.returncode != 0
    assert '"ok": true' not in r.stdout
    assert "no TPU" in r.stdout


@pytest.mark.parametrize(
    "phase", ["headline", "coverage", "parity", "cli", "serve"]
)
def test_single_chip_phase_tiny(phase):
    sz = chip_smoke.TINY
    fn = {
        "headline": lambda: chip_smoke.phase_headline(sz),
        "coverage": lambda: chip_smoke.phase_coverage(sz, require_pallas=False),
        "parity": lambda: chip_smoke.phase_parity(sz),
        "cli": lambda: chip_smoke.phase_cli(sz),
        "serve": lambda: chip_smoke.phase_serve(sz),
    }[phase]
    assert chip_smoke.run_phases([(phase, fn)])


def test_four_chip_phase_on_virtual_devices():
    devices = jax.devices()[:4]
    assert len(devices) == 4
    assert chip_smoke.run_phases([
        ("four_chip", lambda: chip_smoke.phase_four_chip(
            chip_smoke.TINY, devices)),
    ])


def test_reference_stats_check_catches_a_broken_line():
    line = (
        "Node 0: Generated 1, Received 2, Forwarded 2, Total sent 6, "
        "Total processed 3, Peer count 2, Socket connections 2\n"
    )
    totals = (
        "Total shares generated: 1\nTotal shares received: 2\n"
        "Total shares forwarded: 2\nTotal shares sent: 6\n"
    )
    assert chip_smoke.check_reference_stats(line + totals, 1)["sent"] == 6
    with pytest.raises(AssertionError, match="processed"):
        chip_smoke.check_reference_stats(
            line.replace("processed 3", "processed 4") + totals, 1
        )
