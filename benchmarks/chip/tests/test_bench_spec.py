"""``BENCHMARK.json`` against the rules a benchmark file has to keep, and
every file it names found by that name. Reads JSON and imports the
metric readers; nothing here touches a device."""
import json
import os
import re
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
CHIP = os.path.dirname(HERE)
ROOT = os.path.dirname(os.path.dirname(CHIP))
sys.path[:0] = [CHIP, os.path.join(ROOT, "src")]

import harness  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}


@pytest.fixture(scope="module")
def spec():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def one_line(text: str) -> bool:
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level_keys_and_command(spec):
    assert set(spec) == TOP_KEYS
    assert 1 <= len(spec["paths"]) <= 16
    for p in spec["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_./\-]{1,200}", p)
        assert not p.startswith("/") and ".." not in p.split("/")
        assert os.path.isdir(os.path.join(ROOT, p))
    cmd = spec["command"]
    assert 1 <= len(cmd) <= 32 and all(one_line(w) for w in cmd)
    for word in cmd[1:]:
        if os.path.exists(os.path.join(ROOT, word)):
            assert any(word.startswith(p + "/") for p in spec["paths"])
    assert isinstance(spec["run_seconds"], int)
    assert 1 <= spec["run_seconds"] <= 51


def test_names_units_and_keys(spec):
    names = []
    for c in spec["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert one_line(c["source"]) and one_line(c["why"])
        assert len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])
        names.append(c["name"])
    for w in spec["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and one_line(w["why"])
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        names.append(w["name"])
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
        names.append(m["name"])
    for m in spec["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in spec["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "source", "layer", "moves"}
        assert one_line(m["layer"])
    assert all(NAME.match(n) for n in names)
    assert len(names) == len(set(names))
    pairs = [(w["config"], w["traffic"]) for w in spec["workloads"]]
    assert len(pairs) == len(set(pairs))
    four = sum(w["chips"] == 4 for w in spec["workloads"])
    assert four <= max(1, len(spec["workloads"]) // 2)


def test_every_file_is_found_by_its_name(spec):
    for c in spec["configs"]:
        assert c["file"] == os.path.relpath(harness.config_file(c["name"]),
                                            ROOT)
        config = harness.load_json(harness.config_file(c["name"]))
        assert os.path.exists(harness.entry_file(config["entry"]))
        # every key that the cut changed is a key of the file
        assert set(c["reduced"]) <= set(config)
        assert config["reduced"] == c["reduced"]
    for w in spec["workloads"]:
        cell = harness.load_cell(spec, w["name"])
        assert cell.chips == w["chips"] == cell.config["chips"]
        assert hasattr(cell.entry, "Deployment")
    for m in spec["end_to_end"] + spec["per_layer"]:
        mod = harness.load_module(harness.metric_file(m["name"]))
        assert callable(mod.read)


def test_each_cell_reports_setup_another_end_to_end_and_a_layer(spec):
    used_configs = set()
    for w in spec["workloads"]:
        cell = harness.load_cell(spec, w["name"])
        e2e = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert cell.per_layer
        for m in cell.per_layer:
            assert m["moves"] in e2e, (w["name"], m["name"])
        used_configs.add(w["config"])
    assert used_configs == {c["name"] for c in spec["configs"]}


def test_every_listed_workload_exists(spec):
    cells = {w["name"] for w in spec["workloads"]}
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert set(m.get("workloads", ())) <= cells


def test_the_command_refuses_a_cpu(spec):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cmd = [sys.executable, os.path.join(CHIP, "run.py"), "--workload",
           spec["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
           "--trace", "0"]
    out = subprocess.run(cmd, cwd=ROOT, env=env, capture_output=True,
                         text=True, timeout=300)
    assert out.returncode != 0
    assert "{" not in out.stdout
    assert "no TPU" in out.stderr


@pytest.mark.parametrize("status", [
    "Name:\tpython3\nVmRSS:\t  2097152 kB\n",       # no VmHWM line
    "Name:\tpython3\n",                              # no memory lines
    None,                                            # no file at all
])
def test_host_memory_log_never_stops_a_run(status, tmp_path):
    path = str(tmp_path / "status")
    if status is not None:
        with open(path, "w") as f:
            f.write(status)
    line = harness.host_memory(path)
    assert line.startswith("host RSS ") and "peak " in line
    if status and "VmRSS" in status:
        assert "host RSS 2.00 GiB" in line


def test_peaks_are_keyed_by_device_kind():
    import peaks
    assert peaks.peak("TPU v5 lite", "hbm_bytes_per_s") == 819e9
    assert peaks.peak("TPU v5 lite", "bf16_flops") == 197e12
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peak("cpu", "hbm_bytes_per_s")
