"""Whole runs of the CFD cells through the harness with four devices
visible, as on a host with four chips: four virtual CPU devices, in a
process of its own because JAX fixes its device count when it starts.
The four-chip cell puts one server on each chip and comes out correct,
and not correct with its control or with the halo exchange left out;
the one-chip cell keeps both its servers on the one chip it was given.
The harness's look for a chip is skipped; nothing else is."""
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
CHIP = os.path.dirname(HERE)
ROOT = os.path.dirname(os.path.dirname(CHIP))

FOUR = "cfd_d2q9_8k_4srv.ckpt5"
ONE = "cfd_d2q9_8k_2srv.ckpt5"

# one run of a cell at a tiny lattice; its last line of output gives the
# devices each job's outputs came from, per server
SCRIPT = """
import json, sys, time
from contextlib import nullcontext
from unittest import mock

import harness
from repro.apps import lbm

workload, variant = sys.argv[1:]
cell = harness.load_cell(harness.load_json(harness.spec_path()), workload)
cell.config["lattice"].update(height=32, width=64)
harness.enable_compile_cache = lambda: "off"
made = []


class Kept(cell.entry.Deployment):
    def __init__(self, *args):
        super().__init__(*args)
        made.append(self)


cell.entry.Deployment = Kept

with {"correct": nullcontext(), "control": cell.entry.control(),
      "no_halo": mock.patch.object(lbm, "exchange_halos", lambda s: s),
      }[variant]:
    rc = harness.run(cell, 2**31 + 17, 0.3, False, time.perf_counter(),
                     require_tpu=False)
print(json.dumps({"rc": rc, "outputs": [
    [[d.id for d in devs] for devs in job] for job in made[0].job_devices]}))
"""


def run(workload: str, variant: str) -> tuple:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([CHIP,
                                           os.path.join(ROOT, "src")]))
    out = subprocess.run([sys.executable, "-c", SCRIPT, workload, variant],
                         cwd=ROOT, env=env, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode == 0, out.stderr
    *_, result, placed = out.stdout.strip().splitlines()
    placed = json.loads(placed)
    assert placed["rc"] == 0
    return json.loads(result), placed["outputs"]


def test_four_chip_cell_puts_a_server_on_each_chip():
    result, outputs = run(FOUR, "correct")
    assert result["correct"] is True, result
    assert result["device"]["count"] == 4
    assert result["checks"]["jobs_off_their_chips"]["value"] == 0
    assert outputs and all(job == [[0], [1], [2], [3]] for job in outputs)


@pytest.mark.parametrize("variant", ["control", "no_halo"])
def test_four_chip_cell_is_not_correct_when_broken(variant):
    result, _ = run(FOUR, variant)
    assert result["correct"] is False, result
    assert result["checks"]["max_abs_df"]["value"] > \
        result["checks"]["max_abs_df"]["limit"]


def test_one_chip_cell_keeps_its_servers_on_its_chip():
    result, outputs = run(ONE, "correct")
    assert result["correct"] is True, result
    assert result["checks"]["jobs_off_their_chips"]["value"] == 0
    assert outputs and all(job == [[0], [0]] for job in outputs)
