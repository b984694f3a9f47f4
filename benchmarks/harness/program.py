"""Glue between the benchmark's data and the program's objects: parameter
trees laid out through a family's ``leaf_map``, model-config checks, device
facts.  Nothing here times or judges anything."""

from __future__ import annotations

import dataclasses
import os
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np


def to_program_tree(rows: list[tuple]):
    """ref weights (stacked, published layout) -> nested dict in program layout."""

    def build(ref: dict) -> dict:
        tree: dict = {}
        for name, layer, path, transpose in rows:
            x = ref[name] if layer is None else ref[name][layer]
            node = tree
            for k in path[:-1]:
                node = node.setdefault(k, {})
            node[path[-1]] = x.T if transpose else x
        return tree

    return build


def tree_get(tree: Any, path: tuple) -> Any:
    for k in path:
        tree = tree[k]
    return tree


def leaf_norms_by_row(rows: list[tuple], tree: Any) -> dict[tuple, float]:
    """(reference name, layer) -> L2 norm of the program's leaf there.  One
    jitted reduction over the whole tree, one transfer of the scalars."""
    leaves = [tree_get(tree, path) for _, _, path, _ in rows]
    norms = jax.jit(lambda xs: [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))) for x in xs])(leaves)
    norms = np.asarray(jax.device_get(norms), np.float64)
    return {(name, layer): float(v) for (name, layer, _, _), v in zip(rows, norms)}


def leaf_diff_norms_by_row(rows: list[tuple], tree: Any, other: Any) -> dict[tuple, float]:
    """As above for ``tree - other`` (two trees of one layout)."""
    a = [tree_get(tree, path) for _, _, path, _ in rows]
    b = [tree_get(other, path) for _, _, path, _ in rows]
    norms = jax.jit(
        lambda xs, ys: [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32) - y.astype(jnp.float32))))
                        for x, y in zip(xs, ys)]
    )(a, b)
    norms = np.asarray(jax.device_get(norms), np.float64)
    return {(name, layer): float(v) for (name, layer, _, _), v in zip(rows, norms)}


def reference_norms(stacked: dict) -> dict[tuple, float]:
    """(name, layer) -> L2 norm, from tensors in the reference's stacked layout."""
    names = sorted(stacked)

    def reduce(xs):
        return [
            jnp.sqrt(jnp.sum(jnp.square(x), axis=tuple(range(1, x.ndim)))) if ".*" in n
            else jnp.sqrt(jnp.sum(jnp.square(x)))
            for n, x in zip(names, xs)
        ]

    vals = jax.device_get(jax.jit(reduce)([stacked[n] for n in names]))
    out = {}
    for n, v in zip(names, vals):
        v = np.asarray(v, np.float64)
        if ".*" in n:
            out.update({(n, i): float(x) for i, x in enumerate(v)})
        else:
            out[(n, None)] = float(v)
    return out


SAMPLE = 4096  # elements of each leaf kept for the element-wise comparison


def leaf_samples_by_row(rows: list[tuple], tree: Any) -> dict[tuple, np.ndarray]:
    """(reference name, layer) -> the first ``SAMPLE`` elements of the leaf,
    read in the reference's (published) element order."""
    leaves = [(tree_get(tree, path), tr) for _, _, path, tr in rows]
    got = jax.jit(lambda xs: [(x.T if tr else x).reshape(-1)[:SAMPLE].astype(jnp.float32)
                              for x, tr in zip(xs, [t for _, t in leaves])])([x for x, _ in leaves])
    got = jax.device_get(got)
    return {(name, layer): np.asarray(v, np.float64) for (name, layer, _, _), v in zip(rows, got)}


def reference_samples(stacked: dict) -> dict[tuple, np.ndarray]:
    """The same elements from tensors in the reference's stacked layout."""
    names = sorted(stacked)
    got = jax.device_get(jax.jit(lambda xs: [
        x.reshape(x.shape[0], -1)[:, :SAMPLE] if ".*" in n else x.reshape(-1)[:SAMPLE]
        for n, x in zip(names, xs)])([stacked[n] for n in names]))
    out = {}
    for n, v in zip(names, got):
        v = np.asarray(v, np.float64)
        if ".*" in n:
            out.update({(n, i): row for i, row in enumerate(v)})
        else:
            out[(n, None)] = v
    return out


def patched_model_config(config: Any, checks: dict, overrides: dict) -> Any:
    """The program's model config with the file's run values set, after
    checking that its sizes ARE the file's (a cell runs what its file says)."""
    for k, want in checks.items():
        have = getattr(config, k)
        if have != want:
            raise SystemExit(f"program model config {k}={have!r} differs from the configuration file's {want!r}")
    return dataclasses.replace(config, **overrides)


def register_bench_model(cfg: dict, adapter: Any) -> str:
    """Put the configuration file's model into the program's registry under
    ``<registry_name>.bench`` (sizes checked, run values set) and return that
    name: the cell then goes through ``load_model`` like any named model."""
    from distributed_llms_example_tpu.models import registry

    table = getattr(registry, adapter.REGISTRY_TABLE)
    name = cfg["registry_name"] + ".bench"
    table[name] = patched_model_config(
        table[cfg["registry_name"]], adapter.program_config_checks(cfg), adapter.program_config_overrides(cfg))
    return name


def device_facts(chips: int) -> dict:
    devs = jax.devices()
    d = devs[0]
    return {"platform": d.platform, "kind": d.device_kind, "count": len(devs), "chips_used": chips}


def memory_peak_bytes(chips: int) -> int:
    """Peak bytes in use on the fullest chip used, as the runtime reports it
    (on this TPU runtime: the high-water mark of live arrays; a program's
    temporaries are not in it — PERF.md)."""
    peak = 0
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def cache_dir_stats(path: str) -> dict:
    n = size = 0
    if os.path.isdir(path):
        for f in os.scandir(path):
            if f.is_file():
                n += 1
                size += f.stat().st_size
    return {"entries": n, "bytes": size}
