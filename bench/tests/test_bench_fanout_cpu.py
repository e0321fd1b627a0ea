"""The checkpoint fan-out cell on four virtual CPU devices: a sound run is
correct; the float8 control and every fault the cell can have (the
exchange left out, half of the leaves left out, an answer altered on one
chip, in the last rollout or only before it) come out incorrect."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent


@pytest.fixture(scope="module")
def cases():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [str(HERE.parents[1] / "src"), env.get("PYTHONPATH", "")])
    p = subprocess.run([sys.executable, str(HERE / "fanout_cpu_check.py")],
                       capture_output=True, text=True, env=env, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_sound_fanout_is_correct(cases):
    assert cases["sound"]["correct"] is True
    assert cases["sound"]["elements_off"] == 0
    assert cases["sound"]["metrics"] == ["fanout_GBps", "setup_s"]


@pytest.mark.parametrize("fault", ["no_exchange", "half_leaves", "altered"])
def test_fault_is_caught(cases, fault):
    assert cases[fault]["correct"] is False
    assert cases[fault]["elements_off"] > 0


def test_fault_before_the_last_rollout_is_caught(cases):
    """The check also compares a rollout drawn from the seed, so a fault
    that spares the last rollout is caught."""
    assert cases["earlier_fault"]["drawn_last"] is False
    assert cases["earlier_fault"]["elements_off"] > 0


def test_float8_control_fails_the_exact_limit(cases):
    assert cases["control"]["elements_off"] > 0


def test_traced_run_has_breakdown_and_checks_last(cases):
    assert "breakdown" in cases["traced"] and "checks" in cases["traced"]
