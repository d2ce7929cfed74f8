"""Canonical bench partition-artifact recipe, shared by bench.py and
the probe scripts.

partitions/ is not git-tracked, so artifacts vanish between rounds;
every consumer goes through :func:`ensure` (or :func:`build_artifact`
for non-canonical datasets) instead of failing — or re-implementing
the recipe: the dataset string, the ``c2`` generator revision, the
cluster suffix and the reorder suffix are artifact *identity* and must
live in exactly one place.

No reference counterpart: the reference caches DGL partition JSONs on
disk keyed by graph_name (helper/utils.py:137); this is the analogous
cache plus self-describing naming for the synthetic bench graphs.
"""

from __future__ import annotations

import os
import re
import shutil
import time

GEN_REV = "2"  # synthetic-graph generator revision (deduped pairs)

# regex over the self-describing artifact basename:
#   bench-{reddit|small}-{n_parts}-c{rev}-s{cluster_size}[-r{reorder}]
# (no -r suffix == reorder "none": pre-reorder names stay valid keys)
_NAME_RE = re.compile(
    r"bench-(reddit|small)-(\d+)-c(\d+)-s(\d+)"
    r"(?:-r(degree-bfs|degree|bfs))?")


def artifact_path(n_parts: int, cluster_size: int, small: bool = False,
                  root: str = "partitions",
                  reorder: str = "none") -> str:
    from .partitioner import cluster_suffix, reorder_suffix

    name = f"bench-small-{n_parts}" if small else f"bench-reddit-{n_parts}"
    return os.path.join(root, f"{name}-c{GEN_REV}-"
                              f"{cluster_suffix(cluster_size)}"
                              f"{reorder_suffix(reorder)}")


def parse_artifact_name(path: str):
    """(small, n_parts, cluster_size, reorder) from a bench artifact
    path, or None when the basename is not a bench artifact (exact
    match only — substring guards once confused s1024 with s10240)."""
    m = _NAME_RE.fullmatch(os.path.basename(path))
    if not m or m.group(3) != GEN_REV:
        return None
    return (m.group(1) == "small", int(m.group(2)), int(m.group(4)),
            m.group(5) or "none")


def _publish(sg, path: str, log) -> None:
    """Atomically move a built ShardedGraph save into ``path``.

    Race-tolerant: builds land in a per-pid temp sibling; whoever
    renames first wins, losers discard their copy. A stale
    manifest-less dir at ``path`` (a save killed mid-write before this
    scheme existed) is renamed aside into a per-pid trash sibling and
    deleted THERE — readers never observe a half-deleted dir at
    ``path``, and the validity check happens immediately before the
    single atomic rename, so the window in which a concurrent winner's
    fresh artifact could be displaced is one rename wide (not the
    length of an rmtree). Even then both builds are deterministic
    copies of the same artifact, so the re-publish is identical.
    """
    from . import ShardedGraph

    tmp = f"{path}.tmp-{os.getpid()}"
    if os.path.exists(tmp):
        shutil.rmtree(tmp)
    sg.save(tmp)
    try:
        os.rename(tmp, path)
        return
    except OSError:
        # load-bearing but locally handled (storage-fault audit): the
        # fall-through below re-checks, displaces, retries, and RAISES
        # RuntimeError when nothing publishable lands — publish failure
        # is never silent
        pass
    # re-check RIGHT before displacing anything: a concurrent winner
    # may have renamed a valid artifact into place since our failed
    # rename above
    if not ShardedGraph.exists(path) and os.path.isdir(path):
        log(f"# replacing stale non-artifact dir at {path}")
        trash = f"{path}.trash-{os.getpid()}"
        try:
            os.rename(path, trash)  # aside, never rmtree in place
        except OSError:
            pass  # a concurrent builder displaced it first
        else:
            shutil.rmtree(trash, ignore_errors=True)
        try:
            os.rename(tmp, path)
            return
        except OSError:
            pass  # concurrent builder racing on the same stale dir
    if ShardedGraph.exists(path):  # a concurrent builder won
        shutil.rmtree(tmp, ignore_errors=True)
        return
    raise RuntimeError(f"could not publish artifact into {path} "
                       f"(build left at {tmp})")


def build_artifact(dataset: str, n_parts: int, cluster_size: int,
                   path: str, log=print, reorder: str = "none"):
    """Build + publish the partition artifact for ``dataset`` at
    ``path``; returns the in-memory ShardedGraph (cache_dir set). Pure
    host numpy — no jax import, safe from a chip-backend process."""
    from . import ShardedGraph
    from ..graph import load_data
    from .partitioner import locality_clusters, partition_graph

    t0 = time.perf_counter()
    g = load_data(dataset)
    log(f"# loaded {dataset} ({time.perf_counter()-t0:.1f}s)")
    parts = partition_graph(g, n_parts, method="metis", obj="vol", seed=0)
    cluster = locality_clusters(g, target_size=cluster_size, seed=0)
    sg = ShardedGraph.build(g, parts, n_parts=n_parts, cluster=cluster,
                            reorder=reorder)
    _publish(sg, path, log)
    log(f"# built {path} ({time.perf_counter()-t0:.1f}s)")
    sg.cache_dir = path  # derived kernel tables cache with the artifact
    return sg


def ensure(path: str, log=print):
    """Load the bench artifact at ``path``, building it first if
    missing; returns the :class:`ShardedGraph`."""
    from . import ShardedGraph

    if ShardedGraph.exists(path):
        return ShardedGraph.load(path)
    parsed = parse_artifact_name(path)
    if parsed is None:
        raise FileNotFoundError(
            f"{path}: artifact missing and not a canonical bench name "
            f"(expected bench-{{reddit|small}}-N-c{GEN_REV}-sC"
            f"[-rREORDER])")
    small, n_parts, cluster_size, reorder = parsed
    dataset = "synthetic:10000:20:64:16" if small else "synthetic-reddit"
    return build_artifact(dataset, n_parts, cluster_size, path, log=log,
                          reorder=reorder)


def resolve_reorder(n_parts: int, cluster_size: int, small: bool,
                    root: str, reorder: str, log=print) -> str:
    """Resolve ``--reorder auto`` to a concrete artifact layout.

    Preference order: (1) any already-built bench artifact for this
    shape (cheapest — reuse what exists, reordered variants first);
    (2) otherwise a MEASURED decision: build the dataset graph once,
    time a sample of whole destination tile-rows under the
    'none' and 'degree-bfs' layouts (ops.tuner.choose_reorder) and
    take the winner. Concrete modes pass through unchanged, so
    callers can always treat the return value as artifact identity.
    """
    if reorder != "auto":
        return reorder
    from . import ShardedGraph

    candidates = ["degree-bfs", "degree", "bfs", "none"]
    for mode in candidates:
        p = artifact_path(n_parts, cluster_size, small, root, mode)
        if ShardedGraph.exists(p):
            log(f"# --reorder auto: reusing existing artifact {p}")
            return mode
    from ..graph import load_data
    from ..ops.tuner import choose_reorder

    dataset = "synthetic:10000:20:64:16" if small else "synthetic-reddit"
    g = load_data(dataset)
    mode, timings = choose_reorder(g, log=log)
    log(f"# --reorder auto -> {mode} (measured {timings})")
    return mode
