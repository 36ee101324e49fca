"""Checkpointing with LTSP-scheduled archive restore.

Two tiers:

* **hot tier** — plain directory of ``.npy`` leaves + manifest (save/restore
  for crash recovery, bit-exact, no external deps);
* **archive tier** — checkpoint shards written sequentially to the simulated
  tape library.  A multi-pod restore requests every shard once per consumer
  pod (that multiplicity is exactly LTSP's request multiplicity); the restore
  read order is produced by the paper's DP/SimpleDP schedulers, minimising the
  *mean* shard arrival time so pods start resharding work as early as
  possible instead of waiting for a positional sweep.
"""

from __future__ import annotations

import json
import pathlib
import re
from typing import Any

import jax
import numpy as np

from ..storage.tape import ReadPlan, TapeLibrary

__all__ = [
    "save_checkpoint",
    "load_checkpoint",
    "archive_to_tape",
    "plan_restore",
]

_SEP = "::"


def _flatten_with_names(tree) -> list[tuple[str, np.ndarray]]:
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    out = []
    for path, leaf in flat:
        name = _SEP.join(
            str(p.key) if isinstance(p, jax.tree_util.DictKey) else str(p.idx)
            for p in path
        )
        out.append((name, np.asarray(leaf)))
    return out


def save_checkpoint(path: str | pathlib.Path, step: int, **trees: Any) -> None:
    """Write named pytrees (e.g. ``params=..., opt_state=...``) + manifest."""
    path = pathlib.Path(path)
    path.mkdir(parents=True, exist_ok=True)
    manifest: dict[str, Any] = {"step": int(step), "trees": {}}
    for tree_name, tree in trees.items():
        treedef = jax.tree_util.tree_structure(tree)
        leaves = _flatten_with_names(tree)
        manifest["trees"][tree_name] = {
            "treedef": str(treedef),
            "leaves": [
                {"name": n, "shape": list(a.shape), "dtype": str(a.dtype)}
                for n, a in leaves
            ],
        }
        for i, (name, arr) in enumerate(leaves):
            safe = re.sub(r"[^A-Za-z0-9_.-]", "_", f"{tree_name}{_SEP}{name}")
            np.save(path / f"{i:05d}_{safe}.npy", arr)
        manifest["trees"][tree_name]["files"] = [
            f"{i:05d}_" + re.sub(r"[^A-Za-z0-9_.-]", "_", f"{tree_name}{_SEP}{n}") + ".npy"
            for i, (n, _) in enumerate(leaves)
        ]
    (path / "manifest.json").write_text(json.dumps(manifest))


def load_checkpoint(path: str | pathlib.Path, **templates: Any):
    """Restore pytrees by structure templates -> (step, {name: tree})."""
    path = pathlib.Path(path)
    manifest = json.loads((path / "manifest.json").read_text())
    out = {}
    for tree_name, template in templates.items():
        info = manifest["trees"][tree_name]
        arrays = [np.load(path / f) for f in info["files"]]
        treedef = jax.tree_util.tree_structure(template)
        out[tree_name] = jax.tree_util.tree_unflatten(treedef, arrays)
    return manifest["step"], out


# ---------------------------------------------------------------------------
# archive tier (tape-backed) — the paper's technique as a framework feature
# ---------------------------------------------------------------------------
def archive_to_tape(
    library: TapeLibrary, ckpt_name: str, params, bytes_per_elem: int = 4
) -> list[str]:
    """Append every leaf of a checkpoint sequentially to the tape library."""
    names = []
    for leaf_name, arr in _flatten_with_names(params):
        fname = f"{ckpt_name}/{leaf_name}"
        library.store(fname, max(1, arr.size * bytes_per_elem))
        names.append(fname)
    return names


def plan_restore(
    library: TapeLibrary,
    shard_names: list[str],
    consumers_per_shard: int | dict[str, int] = 1,
    policy: str = "simpledp",
    backend: str | None = None,
    cache=None,
    *,
    context=None,
) -> list[ReadPlan]:
    """LTSP-scheduled restore: order shard reads to minimise mean arrival.

    ``consumers_per_shard`` is the request multiplicity (e.g. the number of
    pods that need the shard before they can start their reshard step).
    ``policy`` selects any registered solver; ``context`` (an
    :class:`repro.core.ExecutionContext`, defaulting to the library's own)
    selects backend/cache/bucketing options — with the library context carrying
    a :class:`repro.core.SolveCache`, a restore re-planned against an
    unchanged archive is pure cache hits.  Device backends plan every
    cartridge in a few size-bucketed launches.  ``backend=``/``cache=`` are
    the deprecated pre-context spellings (warn, then fold into a context).
    """
    from ..core.context import resolve_context

    ctx = resolve_context(
        context, backend=backend, cache=cache, default=library.context
    )
    if isinstance(consumers_per_shard, int):
        requests = {n: consumers_per_shard for n in shard_names}
    else:
        requests = dict(consumers_per_shard)
    return library.schedule(requests, policy=policy, context=ctx)
