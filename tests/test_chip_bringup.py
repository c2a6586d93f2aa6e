"""Bring-up contracts that need no chip and build no service: where the
compile cache goes, and that chip_smoke.py keeps its parent off JAX and
fails fast without a TPU."""

import ast
import os
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent


def test_compile_cache_placement(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR set: the helper names no directory.
    Unset: the one fixed in-checkout path, never a temp/pid/time one."""
    import jax

    from fishnet_tpu.utils import compile_cache

    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv(compile_cache.ENV_VAR, "/somewhere/else")
        jax.config.update("jax_compilation_cache_dir", None)
        assert compile_cache.configure() is None
        assert jax.config.jax_compilation_cache_dir is None
        assert compile_cache.cache_dir() == "/somewhere/else"

        monkeypatch.delenv(compile_cache.ENV_VAR)
        expected = str(REPO / ".jax_cache")
        assert compile_cache.configure() == expected
        assert compile_cache.configure() == expected  # stable across calls
        assert jax.config.jax_compilation_cache_dir == expected
        assert compile_cache.cache_dir() == expected
        # The small bucket programs compile in well under a second.
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0
        assert jax.config.jax_persistent_cache_min_entry_size_bytes == 0
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_chip_smoke_parent_stays_off_jax_and_fails_without_tpu():
    script = REPO / "chip_smoke.py"
    ast.parse(script.read_text())
    # Importing the script and its parent-side dependency (the fake
    # server) must not pull jax in: the parent never holds the chip.
    probe = (
        "import sys; sys.path.insert(0, %r); import chip_smoke; "
        "import tests.fake_server; "
        "assert 'jax' not in sys.modules, 'parent imported jax'; "
        # The driver reads the last stdout line: these keys, no others.
        "import json; line = json.loads(chip_smoke.result_line("
        "{'platform': 'tpu', 'kind': 'TPU v5 lite', 'count': 1})); "
        "assert line == {'ok': True, 'device': {'platform': 'tpu', "
        "'kind': 'TPU v5 lite', 'count': 1}}, line"
    ) % str(REPO)
    subprocess.run([sys.executable, "-c", probe], check=True, cwd=REPO,
                   timeout=60)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(script)], cwd=REPO, env=env,
        capture_output=True, text=True, timeout=60,
    )
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert '"ok"' not in proc.stdout  # no result line on failure


def test_fleet_supervisor_enforces_one_process_per_chip(monkeypatch):
    """A device-owning child claims the host's chips whole: a second
    owner, or a parent that has touched JAX, is refused — unless the
    fleet is held to the CPU (this suite's own venue)."""
    import pytest

    from fishnet_tpu.cluster.supervisor import (
        ChipOwnershipError,
        FleetSupervisor,
        ProcSpec,
    )

    nnue = ("--engine", "tpu-nnue")
    assert ProcSpec("a", extra_args=nnue).owns_device()
    assert ProcSpec("a", extra_args=("--engine=az-mcts",)).owns_device()
    assert ProcSpec("e", role="evaluator").owns_device()
    assert not ProcSpec("m").owns_device()  # supervisor default: mock
    assert not ProcSpec("f", role="frontend", extra_args=nnue).owns_device()

    def fleet(*specs):
        return FleetSupervisor("http://127.0.0.1:1/fishnet", list(specs))

    two = fleet(ProcSpec("a", extra_args=nnue), ProcSpec("b", extra_args=nnue))
    split = fleet(ProcSpec("e", role="evaluator"),
                  ProcSpec("f", role="frontend", extra_args=nnue))
    two._check_chip_ownership()  # JAX_PLATFORMS=cpu (conftest): allowed
    monkeypatch.delenv("JAX_PLATFORMS")
    with pytest.raises(ChipOwnershipError, match="one process at a time"):
        two._check_chip_ownership()
    # One owner, but this (pytest) process has imported jax.
    with pytest.raises(ChipOwnershipError, match="never touches JAX"):
        split._check_chip_ownership()
    fleet(ProcSpec("m"), ProcSpec("n"))._check_chip_ownership()  # no owner
