"""chip_smoke.py's phases and its numpy reference checker, on CPU at a tiny
scale (the chip run itself needs a TPU; its device check is not called
here)."""
import importlib.util
import os

import jax
import numpy as np
import pytest

_PATH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "chip_smoke.py")
SCALE = 9
MEM_BYTES = 40_000   # a 2,000-edge MemGraph: the run flushes and compacts
QUERIES = 128


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", _PATH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    mod._import_repro()
    return mod


@pytest.fixture(scope="module")
def ref(smoke):
    _src, _dst, ops = smoke.make_stream(SCALE, 0)
    return smoke.Reference(ops, 1 << SCALE)


def test_single_chip_phases_pass_on_cpu(smoke, tmp_path):
    lines = []
    failures = smoke.run_single(SCALE, 0, workdir=str(tmp_path / "db"),
                                mem_bytes=MEM_BYTES, n_queries=QUERIES,
                                log=lines.append)
    assert failures == [], "\n".join(lines)
    checks = [ln for ln in lines if ln.startswith("check ")]
    assert len(checks) == 7 and all(ln.endswith("PASS") for ln in checks)
    assert not (tmp_path / "db").exists()


def test_sharded_phases_pass_on_cpu(smoke):
    lines = []
    failures = smoke.run_sharded([jax.devices()[0]] * 4, SCALE, 0,
                                 mem_bytes=MEM_BYTES, n_queries=QUERIES,
                                 log=lines.append)
    assert failures == [], "\n".join(lines)
    assert "check shard_placement: PASS" in lines


def test_reference_replays_newest_update_wins(smoke):
    ins, dele = False, True
    a = np.array
    ops = [(ins, a([0, 1, 0]), a([1, 0, 2])),   # 0-1, 1-0, 0-2
           (dele, a([0]), a([1])),               # delete 0-1
           (ins, a([2, 0]), a([0, 1])),          # 2-0, re-insert 0-1
           (dele, a([1]), a([0]))]               # delete 1-0
    ref = smoke.Reference(ops, 4)
    assert ref.neighbors(0).tolist() == [1, 2]
    assert ref.neighbors(1).tolist() == []
    assert ref.neighbors(2).tolist() == [0]
    assert ref.bfs(0).tolist()[:3] == [0.0, 1.0, 1.0]
    assert ref.bfs(0)[3] == smoke.BFS_INF


def test_checker_rejects_wrong_neighbors(smoke, ref):
    vs = np.nonzero(ref.deg > 1)[0][:8]
    good = [ref.neighbors(v).copy() for v in vs]
    assert smoke.check_neighbors(ref, vs, good) == ""
    for wrong in ([g[:-1] for g in good],                 # an edge lost
                  [np.r_[g, 1 << SCALE] for g in good],   # one too many
                  [g[::-1] for g in good],                # unordered
                  good[:-1]):                             # an answer lost
        assert smoke.check_neighbors(ref, vs, wrong) != ""


def test_checker_rejects_wrong_analytics(smoke, ref):
    pr = ref.pagerank(smoke.PR_ITERS)
    assert smoke.check_pagerank(pr, pr.astype(np.float32)) == ""
    off = pr.copy()
    off[3] += 2 * smoke.PR_MAX_ABS
    assert smoke.check_pagerank(pr, off) != ""
    nan = pr.copy()
    nan[0] = np.nan
    assert smoke.check_pagerank(pr, nan) != ""
    lv = ref.bfs(int(np.argmax(ref.deg)))
    assert smoke.check_bfs(lv, lv.copy()) == ""
    far = lv.copy()
    far[np.argmax(lv < smoke.BFS_INF)] += 1
    assert smoke.check_bfs(lv, far) != ""
    assert smoke.check_csr(ref.voff, ref.dst, ref.voff, ref.dst) == ""
    assert smoke.check_csr(ref.voff, ref.dst, ref.voff, ref.dst[::-1]) != ""


def _run_script(path, cwd):
    import subprocess
    import sys
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, path], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_script_refuses_cpu(tmp_path):
    r = _run_script(_PATH, str(tmp_path))
    assert r.returncode != 0
    assert "no TPU" in r.stderr
    assert '"ok"' not in r.stdout


def test_script_alone_fails(tmp_path):
    import shutil
    alone = tmp_path / "chip_smoke.py"
    shutil.copy(_PATH, alone)
    r = _run_script(str(alone), str(tmp_path))
    assert r.returncode != 0
    assert '"ok"' not in r.stdout


@pytest.mark.parametrize("scale", ["17", "21"])
def test_scale_cut_is_bounded(smoke, scale, capsys):
    """--scale accepts only MIN_SCALE..FULL_SCALE; a cut below 18 (or a
    scale past the Graph500 setting) is refused before JAX is touched."""
    with pytest.raises(SystemExit) as e:
        smoke.main(["--scale", scale])
    assert e.value.code == 2
    assert '"ok"' not in capsys.readouterr().out
