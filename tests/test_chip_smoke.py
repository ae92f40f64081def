"""chip_smoke.py's answer checks, and its refusal to report a result
anywhere but on a TPU (the chip run itself happens on the chip)."""
import importlib.util
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from repro.core.backtrack import backtrack_deadend
from repro.data.graph_gen import er_labeled_graph, query_set

ROOT = Path(__file__).resolve().parent.parent


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def case():
    data = er_labeled_graph(40, 120, 3, seed=6)
    q = query_set(data, 4, 1, seed=3)[0]
    full = backtrack_deadend(q, data, limit=None)
    assert 3 <= full.stats.found     # enough rows to cut a limit from
    return data, q, full


def test_full_answer_must_equal_the_oracle_set(smoke, case):
    data, q, full = case
    rows = [np.asarray(e) for e in full.embeddings][::-1]   # any order
    assert smoke.check_answer(q, data, rows, "ok", full, 1000) is None
    assert "differs" in smoke.check_answer(q, data, rows[1:], "ok", full,
                                           1000)
    assert "status" in smoke.check_answer(q, data, rows, "error", full,
                                          1000)


def test_limited_answer_checked_by_count_and_validity(smoke, case):
    data, q, full = case
    limit = full.stats.found - 1
    oracle = backtrack_deadend(q, data, limit=limit)
    rows = [np.asarray(e) for e in full.embeddings[1:]]     # other rows
    assert smoke.check_answer(q, data, rows, "limit", oracle,
                              limit) is None
    assert "embeddings" in smoke.check_answer(q, data, rows[1:], "limit",
                                              oracle, limit)
    dup = rows[:-1] + [rows[0]]
    assert "duplicate" in smoke.check_answer(q, data, dup, "limit",
                                             oracle, limit)
    bad = [r.copy() for r in rows]
    bad[0][0] = bad[0][1]                    # two query vertices, one
    assert "invalid" in smoke.check_answer(q, data, bad, "limit", oracle,
                                           limit)


def test_invalid_embedding_names_the_broken_rule(smoke, case):
    data, q, full = case
    row = tuple(int(v) for v in full.embeddings[0])
    assert smoke.invalid_embedding(q, data, row) is None
    assert "injective" in smoke.invalid_embedding(
        q, data, (row[0],) * len(row))
    other = next(v for v in range(data.n)
                 if data.labels[v] != q.labels[0] and v not in row)
    assert "label" in smoke.invalid_embedding(q, data,
                                              (other,) + row[1:])


def _run(script: Path, cwd: Path) -> subprocess.CompletedProcess:
    env = dict(os.environ, JAX_PLATFORMS="cpu")     # no chip here
    return subprocess.run([sys.executable, str(script)], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def test_refuses_without_a_tpu():
    out = _run(ROOT / "chip_smoke.py", ROOT)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "needs a TPU" in out.stderr


def test_fails_alone_without_the_repo(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path / "chip_smoke.py")
    out = _run(tmp_path / "chip_smoke.py", tmp_path)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
