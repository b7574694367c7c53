"""Each deployment driven through a whole run of the harness on the CPU at
a tiny size: it comes out correct, and it comes out not correct with its
control in the program's place or with the timed path broken underneath.
The harness's look for a chip is skipped; nothing else is."""
import copy
import json
import os
import sys
import time

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
CHIP = os.path.dirname(HERE)
ROOT = os.path.dirname(os.path.dirname(CHIP))
sys.path[:0] = [CHIP, os.path.join(ROOT, "src")]

import harness  # noqa: E402
from repro.apps import lbm  # noqa: E402

SEED = 2**31 + 17               # larger than 32 signed bits hold
SECONDS = 0.3


@pytest.fixture(autouse=True)
def no_compile_cache(monkeypatch):
    """Leave JAX's persistent cache as the other tests of the process
    have it."""
    monkeypatch.setattr(harness, "enable_compile_cache", lambda: "off")


def tiny(workload: str, servers: int = 0) -> harness.Cell:
    """The cell at a tiny lattice, with ``servers`` servers where given;
    they share the CPU backend's one device."""
    cell = harness.load_cell(harness.load_json(harness.spec_path()),
                             workload)
    cell.config = copy.deepcopy(cell.config)
    if "lattice" in cell.config:
        cell.config["lattice"].update(height=32, width=64)
    if servers:
        cell.config["servers"] = servers
    return cell


def run(cell, capsys) -> dict:
    rc = harness.run(cell, SEED, SECONDS, False, time.perf_counter(),
                     require_tpu=False)
    assert rc == 0
    out = capsys.readouterr()
    result = json.loads(out.out.strip().splitlines()[-1])
    assert list(result)[-1] == "checks"
    # the numbers compared close standard error, each with its limit
    tail = out.err.strip().splitlines()[-len(result["checks"]):]
    for line, (name, c) in zip(tail, result["checks"].items()):
        assert line.startswith(f"check {name}: ") and "limit" in line
    return result


CFD = "cfd_d2q9_8k_2srv.ckpt5"
# the CFD path with its own two servers, and with four (one per chip in
# a four-chip deployment)
CELLS = [(CFD, 0), (CFD, 4), ("passthrough_int32.chain", 0)]


@pytest.mark.parametrize("workload,servers", CELLS)
def test_cell_runs_correct(workload, servers, capsys):
    cell = tiny(workload, servers)
    result = run(cell, capsys)
    assert result["correct"] is True, result
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert all(m["value"] > 0 for m in result["metrics"].values())
    assert result["device"]["platform"] == "cpu"


@pytest.mark.parametrize("workload,servers", CELLS)
def test_control_is_not_correct(workload, servers, capsys):
    cell = tiny(workload, servers)
    with cell.entry.control():
        result = run(cell, capsys)
    assert result["correct"] is False, result


def _unchanged(f, tau=0.6):
    return f


_STEP = lbm.lbm_step


def _altered(f, tau=0.6):
    return _STEP(f, tau=tau).at[3, 5, 7].add(1e-3)


def _half(n_servers):
    """Only the first half of the servers' slabs is stepped."""
    step = lbm.lbm_step
    calls = [0]

    def half(f, tau=0.6):
        i = calls[0] % n_servers
        calls[0] += 1
        return step(f, tau) if i < n_servers // 2 else f
    return half


@pytest.mark.parametrize("servers", [2, 4])
@pytest.mark.parametrize("fault", ["unchanged", "half", "no_halo",
                                   "altered"])
def test_cfd_fault_is_not_correct(servers, fault, capsys, monkeypatch):
    cell = tiny(CFD, servers)
    if fault == "unchanged":
        monkeypatch.setattr(lbm, "lbm_step", _unchanged)
    elif fault == "half":
        monkeypatch.setattr(lbm, "lbm_step", _half(cell.config["servers"]))
    elif fault == "no_halo":
        monkeypatch.setattr(lbm, "exchange_halos", lambda slabs: slabs)
    else:
        monkeypatch.setattr(lbm, "lbm_step", _altered)
    result = run(cell, capsys)
    assert result["correct"] is False, result
    assert result["failed"] > 0


@pytest.mark.parametrize("fault", ["unchanged", "altered"])
def test_passthrough_fault_is_not_correct(fault, capsys, monkeypatch):
    cell = tiny("passthrough_int32.chain")
    make = cell.entry.copy_kernel

    def broken(device):
        run_copy = make(device)
        held = []

        def kernel(a):
            if fault == "altered":
                return np.asarray(run_copy(a)) + np.int32(1)
            # unchanged: the output keeps what the first chain left there
            if not held:
                held.append(np.asarray(run_copy(a)))
            return held[0]
        return kernel
    monkeypatch.setattr(cell.entry, "copy_kernel", broken)
    result = run(cell, capsys)
    assert result["correct"] is False, result
    assert result["failed"] == result["attempted"] > 0


def test_same_seed_same_inputs():
    """The lattice and the chain values are made from the seed alone."""
    import jax
    cfd = tiny(CFD)
    a = cfd.entry.initial_state(cfd.config, SEED, jax.devices()[0])
    b = cfd.entry.initial_state(cfd.config, SEED, jax.devices()[0])
    c = cfd.entry.initial_state(cfd.config, SEED + 1, jax.devices()[0])
    assert np.array_equal(a, b) and not np.array_equal(a, c)
    assert a.dtype == np.float32 and a.shape == (9, 32, 64)
