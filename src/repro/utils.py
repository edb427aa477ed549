"""Small shared utilities."""
from __future__ import annotations

import dataclasses
import os
from pathlib import Path

import jax
import jax.numpy as jnp

REPO_ROOT = Path(__file__).resolve().parents[2]


def pytree_dataclass(cls=None, *, meta_fields: tuple[str, ...] = ()):
    """Frozen dataclass registered as a JAX pytree.

    Fields named in ``meta_fields`` are static (hashable aux data); the rest
    are array children.
    """

    def wrap(c):
        c = dataclasses.dataclass(frozen=True)(c)
        data_fields = tuple(
            f.name for f in dataclasses.fields(c) if f.name not in meta_fields
        )
        jax.tree_util.register_dataclass(
            c, data_fields=data_fields, meta_fields=meta_fields
        )
        return c

    return wrap if cls is None else wrap(cls)


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; entry points call this
    before their first compile (library imports never do).

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already read it and
    nothing is changed. Otherwise the cache lives at ``<repo>/.jax_cache``:
    a fixed path, since the directory is part of the cache key. Returns
    the directory in use."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(REPO_ROOT / ".jax_cache"))
    return jax.config.jax_compilation_cache_dir


def cpu_child_env() -> dict:
    """Environment of a child process that simulates devices on the CPU
    (``--xla_force_host_platform_device_count``). The platform is pinned:
    a child that reached for an accelerator would contend with its parent,
    which holds the chip once it has touched JAX."""
    return {"PYTHONPATH": "src", "PATH": os.environ.get("PATH", ""),
            "HOME": os.environ.get("HOME", ""), "JAX_PLATFORMS": "cpu"}


def cdiv(a: int, b: int) -> int:
    return -(-a // b)


def round_up(a: int, b: int) -> int:
    return cdiv(a, b) * b


def tree_bytes(tree) -> int:
    return sum(
        x.size * x.dtype.itemsize
        for x in jax.tree_util.tree_leaves(tree)
        if hasattr(x, "dtype")
    )


def bits_required_jnp(rng: jnp.ndarray) -> jnp.ndarray:
    """ceil(log2(r+1)) for non-negative integer ranges; 0 when r == 0."""
    r = rng.astype(jnp.float32)
    return jnp.where(rng > 0, jnp.floor(jnp.log2(jnp.maximum(r, 1.0))) + 1.0, 0.0).astype(
        jnp.int32
    )
