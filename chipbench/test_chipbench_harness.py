"""The harness without a chip: every cell's files are found by name and
agree with BENCHMARK.json, unknown names fail, and a run anywhere but on
a TPU exits non-zero with no result line."""
import json
import os
import shutil
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)

import body  # noqa: E402
import run  # noqa: E402


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _cells():
    return [w["name"] for w in _bench()["workloads"]]


@pytest.mark.parametrize("name", _cells())
def test_every_cell_loads(name):
    cell = run.load_cell(name)
    assert cell["config"]["name"] == cell["entry"]["config"]
    assert cell["traffic"]["loop"] in ("latency", "throughput")
    assert 0 < cell["limit"]["max_rel_err"] < 1
    assert "setup_s" in cell["end_to_end"] and len(cell["end_to_end"]) >= 2
    assert cell["per_layer"]
    for m in cell["per_layer"]:
        assert isinstance(cell["readers"][m].UNIT, str)


def test_every_workload_file_names_an_existing_config_and_traffic():
    names = set(_cells())
    files = sorted(os.listdir(os.path.join(HERE, "workloads")))
    assert {f[:-len(".json")] for f in files} == names
    for f in files:
        with open(os.path.join(HERE, "workloads", f)) as fh:
            w = json.load(fh)
        body.load_config(w["config"])
        body.load_json("traffic", f"{w['traffic']}.json")
        entry = [e for e in _bench()["workloads"]
                 if e["name"] == f[:-len(".json")]][0]
        assert w["why"] == entry["why"]


def test_configs_declared_in_benchmark_exist():
    for c in _bench()["configs"]:
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
        assert body.load_config(c["name"])["name"] == c["name"]


def test_moves_names_an_end_to_end_metric_of_every_cell():
    bench = _bench()
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        target = e2e[m["moves"]]
        for cell in m.get("workloads", _cells()):
            assert cell in target.get("workloads", [cell]), (m["name"], cell)


def test_unknown_workload_fails():
    with pytest.raises(KeyError, match="no workload"):
        run.load_cell("no-such-cell")


def test_unknown_metric_fails():
    bench = _bench()
    name = bench["workloads"][0]["name"]
    bench["per_layer"] = [dict(bench["per_layer"][0],
                               name="no_such_metric", workloads=[name])]
    with pytest.raises(FileNotFoundError, match="no_such_metric"):
        run.load_cell(name, bench)


def _run_py(cwd, env):
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", _cells()[0],
         "--seed", str(2**31 + 3), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _no_result(stdout):
    return not any(ln.lstrip().startswith("{") for ln in stdout.splitlines())


def test_run_on_cpu_exits_nonzero_without_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = _run_py(ROOT, env)
    assert p.returncode != 0
    assert _no_result(p.stdout)
    assert "TPU" in p.stderr


def test_run_without_the_program_exits_nonzero(tmp_path):
    """A checkout with only BENCHMARK.json and chipbench/ cannot run: past
    the look for a chip (skipped here), the program is not there."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(HERE, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    code = ("import sys; sys.path.insert(0, 'chipbench'); import run; "
            f"sys.exit(run.run(run.load_cell({_cells()[0]!r}), 1, 1.0, "
            "False, device_check=False))")
    p = subprocess.run([sys.executable, "-c", code], cwd=tmp_path, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert _no_result(p.stdout)
    assert "repro" in p.stderr


def test_benchmark_json_shape():
    """The keys, names and units BENCHMARK.json may hold."""
    import re
    bench = _bench()
    name_re = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
    unit_re = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")

    def text(s):
        return isinstance(s, str) and 0 < len(s) <= 200 and "\n" not in s \
            and "\t" not in s

    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= bench["run_seconds"] <= 51
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert name_re.match(c["name"]) and text(c["why"])
        assert all(name_re.match(k) for k in c["reduced"])
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert all(name_re.match(w[k]) for k in ("name", "config", "traffic"))
        assert w["chips"] in (1, 4) and text(w["why"])
    metrics = bench["end_to_end"] + bench["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert text(m["layer"])
    for m in metrics:
        assert name_re.match(m["name"]) and unit_re.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for cell in _cells():
        c = run.load_cell(cell)
        assert "setup_s" in c["end_to_end"] and c["per_layer"]
