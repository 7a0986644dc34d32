"""Small shared utilities."""
from __future__ import annotations

import os
from pathlib import Path
from typing import Any, Tuple

import jax
import numpy as np

# <checkout>/.jax_cache: fixed, so the path part of the cache key never moves
COMPILE_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache for an entry point.
    Where JAX_COMPILATION_CACHE_DIR is set, JAX already reads it and this
    sets nothing; otherwise the cache lives in COMPILE_CACHE_DIR.  Returns
    the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(COMPILE_CACHE_DIR))
    return str(COMPILE_CACHE_DIR)


def path_str(path: Tuple[Any, ...]) -> str:
    """Human-readable pytree path ('layers/0/attn/wq')."""
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        elif hasattr(p, "name"):
            parts.append(str(p.name))
        else:
            parts.append(str(p))
    return "/".join(parts)


def tree_paths(tree) -> list:
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return [(path_str(p), leaf) for p, leaf in flat]


def tree_bytes(tree) -> int:
    return sum(np.prod(l.shape) * l.dtype.itemsize
               for l in jax.tree_util.tree_leaves(tree))


def fmt_bytes(n: float) -> str:
    for unit in ["B", "KiB", "MiB", "GiB", "TiB"]:
        if abs(n) < 1024:
            return f"{n:.2f}{unit}"
        n /= 1024
    return f"{n:.2f}PiB"
