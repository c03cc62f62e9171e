"""The chip benchmark's harness: cells resolve by name, the traffic is a
function of the seed, and no run happens without a TPU."""

import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from _paths import BENCH, ROOT

import harness  # noqa: E402
from tokens import token_pool  # noqa: E402

BENCHMARK = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCHMARK["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("trace", [False, True])
def test_every_cell_resolves_its_files_by_name(cell, trace):
    c = harness.resolve(cell, trace)
    assert c.chips in (1, 4)
    assert c.limits and set(c.limits) <= {"loss_gap", "grad_gap", "change_gap"}
    kind = "per_layer" if trace else "end_to_end"
    for m in BENCHMARK[kind]:
        listed = cell in m.get("workloads", [cell])
        assert (m in c.metrics) == listed
        assert (BENCH / "metrics" / f"{m['name']}.py").is_file()
    names = [m["name"] for m in c.metrics]
    if not trace:
        assert "setup_s" in names and len(names) >= 2
    else:
        assert names
    cfg = harness.program_config(c.config)  # the program's config matches the file
    assert cfg.dtype == c.config["torch_dtype"] and cfg.vocab_size == c.config["vocab_size"]


def test_every_config_file_is_one_configs():
    files = [c["file"] for c in BENCHMARK["configs"]]
    assert len(set(files)) == len(files)
    for c in BENCHMARK["configs"]:
        conf = json.loads((ROOT / c["file"]).read_text())
        assert conf["source"] == c["source"]
        assert conf["reduced"] == c["reduced"]


def test_unknown_cell_is_refused():
    with pytest.raises(KeyError):
        harness.resolve("no-such-cell", False)


def test_token_pool_is_a_function_of_the_seed():
    seed = 2**31 + 977
    a = token_pool(seed, 151936, 2, 4, 2, 64)
    b = token_pool(seed, 151936, 2, 4, 2, 64)
    c = token_pool(seed + 1, 151936, 2, 4, 2, 64)
    assert a.shape == (2, 4, 2, 65) and a.dtype == np.int32
    np.testing.assert_array_equal(a, b)
    assert (a != c).mean() > 0.9
    assert a.min() >= 0 and a.max() < 151936
    rows = a.reshape(-1, 65)
    assert len({r.tobytes() for r in rows}) == len(rows)  # every row differs


def _run(args, cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, *args], cwd=str(cwd), env=env,
                          capture_output=True, text=True, timeout=300)


def test_no_tpu_exits_nonzero_with_no_result():
    proc = _run(["benchmarks/chip/run.py", "--workload", CELLS[0], "--seed",
                 str(2**31 + 5), "--seconds", "1", "--trace", "0"], ROOT)
    assert proc.returncode != 0
    assert "needs" in proc.stderr and "cpu" in proc.stderr
    assert not any(line.startswith("{") for line in proc.stdout.splitlines())


def test_alone_in_a_directory_exits_nonzero(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in BENCHMARK["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run(["benchmarks/chip/run.py", "--workload", CELLS[0], "--seed", "1",
                 "--seconds", "1", "--trace", "0"], tmp_path)
    assert proc.returncode != 0
    assert "No module named 'repro'" in proc.stderr
    assert not any(line.startswith("{") for line in proc.stdout.splitlines())
