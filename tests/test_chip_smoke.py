"""chip_smoke.py and the compile cache it reports on, checked without a chip.

The smoke itself only proves anything on the TPU; what tier-1 can pin is
(a) where the persistent compilation cache lives, (b) that the script's
explicit CPU rehearsal runs both stages and every check it would make on
the chip except the platform ones, and (c) that without the rehearsal
argument and without a chip it fails and prints no result.
"""

import json
import os
import pathlib
import subprocess
import sys

REPO = pathlib.Path(__file__).resolve().parents[1]


def _run(argv, env_extra, timeout):
    env = dict(os.environ, **env_extra)
    # one CPU device: the 8-device mesh of conftest.py only slows compiles
    env.pop("XLA_FLAGS", None)
    return subprocess.run(
        [sys.executable, *argv],
        capture_output=True, text=True, timeout=timeout, cwd=REPO, env=env,
    )


def test_cache_dir_defaults_to_the_checkout(monkeypatch):
    from kubernetes_tpu.utils import compilation_cache as cc

    monkeypatch.delenv(cc.DIR_ENV, raising=False)
    assert cc.cache_dir() == str(REPO / ".jax_cache")
    monkeypatch.setenv(cc.DIR_ENV, "/somewhere/else")
    assert cc.cache_dir() == "/somewhere/else"
    src = pathlib.Path(cc.__file__).read_text()
    assert "tempfile" not in src and "getpid" not in src
    assert "try:" not in src  # one installation: the knobs are set plainly


def test_cache_dir_from_the_environment_is_the_only_one_written(tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: JAX reads it itself, the code sets no
    directory, and nothing lands in the system temp directory."""
    cache, tmp = tmp_path / "cache", tmp_path / "tmp"
    tmp.mkdir()
    r = _run(
        ["-c",
         "import jax, jax.numpy as jnp\n"
         "from kubernetes_tpu.utils.compilation_cache import "
         "enable_persistent_compilation_cache as enable\n"
         "before = jax.config.jax_compilation_cache_dir\n"
         "got = enable()\n"
         "jax.jit(lambda x: x * 2 + 1)(jnp.ones(8)).block_until_ready()\n"
         "print(before, got, jax.config.jax_compilation_cache_dir)\n"],
        {"JAX_COMPILATION_CACHE_DIR": str(cache), "TMPDIR": str(tmp),
         "JAX_PLATFORMS": "cpu"},
        timeout=120,
    )
    assert r.returncode == 0, r.stderr[-2000:]
    assert r.stdout.split() == [str(cache)] * 3
    assert any(cache.iterdir()), "nothing was cached where the variable says"
    assert not any(tmp.iterdir()), list(tmp.iterdir())


def test_chip_smoke_cpu_rehearsal_and_refusal_without_a_chip(tmp_path):
    cache = tmp_path / "cache"
    r = _run(
        ["chip_smoke.py", "--rehearse-cpu", "--nodes", "64",
         "--out", str(tmp_path / "rehearsal")],
        {"JAX_COMPILATION_CACHE_DIR": str(cache)},
        timeout=400,
    )
    assert r.returncode == 0, (r.stdout[-2000:], r.stderr[-3000:])
    *_, observed, last = r.stdout.strip().splitlines()
    # the last line holds the contract's keys and no others
    assert json.loads(last) == {
        "ok": True, "device": {"platform": "cpu", "kind": "cpu", "count": 1},
    }
    assert observed.startswith("observations: ")
    out = json.loads(observed[len("observations: "):])
    assert out["rehearsal"] is True
    assert out["compile_cache"]["dir"] == str(cache)
    assert out["compile_cache"]["start"] == "cold"
    assert out["compile_cache"]["wave_kernel"].get("miss", 0) >= 1
    assert out["stage_a"]["pods_bound"] == 64 + 64 // 5
    assert out["stage_a"]["largest_batch"] == out["stage_a"]["pods_bound"]
    assert {k: v["pods_bound"] for k, v in out["stage_b"].items()} == {
        "basic": 12, "anti_affinity": 12,
    }
    assert out["reference"]["filter_chain_sample"] == 100
    assert out["wal"]["binds_read_back"] == 100
    assert out["audit_passes"] >= 1 and not any(out["safety_counters"].values())
    assert set(out["host_path_pods"]) <= {"small_batch"}
    assert "->" in out["snapshot_placement"]

    # no rehearsal argument, no chip: the scheduler child refuses to start
    # on --platform tpu, the script fails and prints no result line
    r = _run(
        ["chip_smoke.py", "--nodes", "64", "--out", str(tmp_path / "refusal")],
        {"JAX_COMPILATION_CACHE_DIR": str(cache)},
        timeout=300,
    )
    assert r.returncode not in (0, 2), (r.returncode, r.stderr[-2000:])
    assert "scheduler exited early" in r.stderr
    assert "Unable to initialize backend 'tpu'" in r.stderr
    assert not any(
        line.startswith("{") for line in r.stdout.splitlines()
    ), r.stdout[-1000:]
