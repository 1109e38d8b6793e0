"""How the program meets the platform it runs on: Pallas interpret mode
follows the platform alone, process workers stay off the chip, and the
persistent compilation cache sits where the environment or the checkout
says."""
import asyncio
import pathlib

import jax
import pytest

from repro.compile_cache import CACHE_DIR, use_compile_cache
from repro.core import split_model
from repro.kernels.backend import resolve_interpret
from repro.runtime.coordinator import Coordinator, WorkerHandle
from conftest import small_cnn

ROOT = pathlib.Path(__file__).resolve().parent.parent


def test_interpret_follows_platform():
    assert resolve_interpret(None) is (jax.default_backend() != "tpu")
    assert resolve_interpret(True) is True
    assert resolve_interpret(False) is False


@pytest.fixture
def cache_dir_config():
    """Restore JAX's cache directory after a test moves it."""
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_env_is_left_to_jax(monkeypatch, tmp_path,
                                          cache_dir_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    was = jax.config.jax_compilation_cache_dir
    assert use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == was


def test_compile_cache_default_is_fixed_and_ignored(monkeypatch,
                                                    cache_dir_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert use_compile_cache() == str(ROOT / ".jax_cache") == str(CACHE_DIR)
    assert jax.config.jax_compilation_cache_dir == str(CACHE_DIR)
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().split()


def test_process_workers_are_pinned_to_cpu(monkeypatch):
    """A spawned worker must never reach for the chip its parent holds."""
    seen = {}

    async def fake_exec(*args, env=None, **kwargs):
        seen.update(env)

    monkeypatch.setattr(asyncio, "create_subprocess_exec", fake_exec)
    coord = Coordinator(split_model(small_cnn(), [1, 1]), precision="float")

    async def spawn():
        coord.handles = {0: WorkerHandle(0, asyncio.get_running_loop())}
        await coord._spawn_one(0)

    asyncio.run(spawn())
    assert seen["JAX_PLATFORMS"] == "cpu"
