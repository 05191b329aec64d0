"""The harness end to end on the CPU at smoke widths: no TPU -> no result;
each cell's run is correct against its reference; a new configuration,
mix and metric are found by name."""
import json
import os
import shutil
import subprocess
import sys
import textwrap

import pytest

from bench.core import ROOT
from bench.tests import tiny

CELLS = sorted(tiny.CELLS)


def _env():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = os.pathsep.join([str(ROOT), str(ROOT / "src")])
    return env


def test_no_tpu_exits_nonzero_with_no_result():
    p = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload",
         CELLS[0], "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=_env(), capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_only_the_benchmark_files_is_not_enough(tmp_path):
    """A checkout that holds BENCHMARK.json and bench/ alone has no program
    to run: the run fails and prints no result."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed",
         "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_correct_on_cpu(cell):
    from bench import run
    records = []
    res = run.run_cell(cell, 2 ** 31 + 99, 2.0, 0, require_chip=False,
                       overrides=tiny.CELLS[cell], records=records)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0
    assert res["compiles_in_window"] == 0
    assert "setup_s" in res["metrics"]
    assert list(res)[-2:] == ["checks", "_numbers"]
    if "compared_slots" in res["_numbers"]:     # DiT: every slot's rows
        assert res["_numbers"]["compared_slots"] == 2
    else:                                       # LM: the served tokens
        assert res["_numbers"]["compared_tokens"] >= 48
        assert 0 < records[0].extra["pool_peak_pages"] \
            < records[0].extra["num_pages"]


def test_new_config_mix_and_metric_are_found_by_name(tmp_path):
    """Copy the benchmark, add a configuration, a mix, a limits file and a
    metric reader as new files plus BENCHMARK.json entries, and run the new
    cell: the harness finds them all by name."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    b = tmp_path / "bench"
    cfg = json.load(open(b / "configs" / "internlm2_20b.json"))
    cfg.update(tiny.LM["config"])
    json.dump(cfg, open(b / "configs" / "tiny_lm.json", "w"))
    mix = json.load(open(b / "traffic" / "longdoc_backlog.json"))
    mix.update(tiny.LONGDOC["traffic"])
    mix["clients"] = 2
    json.dump(mix, open(b / "traffic" / "two_docs.json", "w"))
    json.dump(tiny.LONGDOC["limits"],
              open(b / "limits" / "tiny_lm.two_docs.json", "w"))
    (b / "metrics" / "requests_due.py").write_text(textwrap.dedent('''
        def read(rec):
            return float(len(rec.requests))   # requests the window counts
    '''))
    spec = json.load(open(tmp_path / "BENCHMARK.json"))
    spec["configs"].append({"name": "tiny_lm", "source": "test",
                            "file": "bench/configs/tiny_lm.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "tiny_lm.two_docs",
                              "config": "tiny_lm", "traffic": "two_docs",
                              "chips": 1, "why": "test"})
    spec["end_to_end"].append({"name": "requests_due", "unit": "requests",
                               "better": "higher", "bound": 0.05,
                               "source": "host_clock",
                               "workloads": ["tiny_lm.two_docs"]})
    json.dump(spec, open(tmp_path / "BENCHMARK.json", "w"))
    script = textwrap.dedent(f'''
        import json, sys
        sys.path[:0] = [{str(tmp_path)!r}, {str(ROOT / "src")!r}]
        from bench import run
        res = run.run_cell("tiny_lm.two_docs", 5, 2.0, 0,
                           require_chip=False)
        res.pop("_numbers")
        print(json.dumps(res))
    ''')
    p = subprocess.run([sys.executable, "-c", script], cwd=tmp_path,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"),
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    res = json.loads(p.stdout.strip().splitlines()[-1])
    assert res["correct"]
    assert res["metrics"]["requests_due"]["value"] > 0
    assert set(res["metrics"]) == {"setup_s", "requests_due"}
