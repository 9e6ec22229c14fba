"""Multi-host scale-out: jax.distributed init + per-host data sharding.

The reference is strictly single-process shared-memory (SURVEY.md §2.3,
§5 — rayon threads, no network layer). The TPU-native distributed story
is SPMD over a global mesh:

  * every host runs this same program; `initialize()` wires them into
    one JAX runtime (coordinator rendezvous over DCN);
  * each host ingests and sketches only its shard of the genome list
    (`host_shard`) — FASTA IO and hashing scale linearly with hosts;
  * the per-host sketch rows are assembled into one globally-sharded
    device array (`global_sketch_matrix`) without any host ever holding
    the full matrix;
  * the pairwise pass is the same `shard_map` program as single-host
    (parallel/mesh.py) — XLA inserts all-gathers over ICI within a
    slice and DCN across slices from the shardings alone.

Single-process runs (including the CPU test mesh) take the same code
path: initialize() is a no-op, host_shard returns everything, and the
"global" mesh is the local one.
"""

from __future__ import annotations

import logging
import os
from typing import List, Optional, Sequence, TypeVar

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

logger = logging.getLogger(__name__)

T = TypeVar("T")


def initialize(coordinator_address: Optional[str] = None,
               num_processes: Optional[int] = None,
               process_id: Optional[int] = None) -> None:
    """Join the multi-host JAX runtime (no-op when single-process).

    Arguments default from the standard env vars
    (JAX_COORDINATOR_ADDRESS, JAX_NUM_PROCESSES, JAX_PROCESS_ID) so
    launchers can configure hosts uniformly; explicit arguments win.
    """
    coordinator_address = (coordinator_address
                           or os.environ.get("JAX_COORDINATOR_ADDRESS"))
    if num_processes is None:
        env = os.environ.get("JAX_NUM_PROCESSES")
        num_processes = int(env) if env else None
    if process_id is None:
        env = os.environ.get("JAX_PROCESS_ID")
        process_id = int(env) if env else None

    if not coordinator_address or (num_processes or 1) <= 1:
        logger.debug("Single-process run; skipping jax.distributed")
        return
    logger.info("Joining distributed runtime as process %s/%s via %s",
                process_id, num_processes, coordinator_address)
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id)


def process_count() -> int:
    return jax.process_count()


def process_index() -> int:
    return jax.process_index()


def host_shard(items: Sequence[T]) -> List[T]:
    """This host's strided shard of a global work list.

    Strided (rather than contiguous) so quality-ordered genome lists
    spread evenly: genome sizes correlate with quality rank, and a
    contiguous split would put all the big genomes on host 0.
    """
    return list(items[process_index()::process_count()])


def global_mesh(axis_name: str = "i") -> Mesh:
    """1-D mesh over every device in the job (all hosts)."""
    return Mesh(np.array(jax.devices()), (axis_name,))


def exchange(tag: str, value: "np.ndarray",
             tiled: bool = False) -> "np.ndarray":
    """One guarded cross-host allgather: every collective in this module
    funnels through here so transient runtime errors get the retry
    policy, and so the fault
    injector can target collectives by site name ("collective.<tag>").

    Retry here is safe ONLY because a failed collective fails on every
    participant — all hosts observe the error and re-enter together.
    There is deliberately no fallback: a collective that stays broken
    after the retry budget must kill the run, not desync it.
    """
    from jax.experimental import multihost_utils

    from galah_tpu.resilience import dispatch as rdispatch

    return rdispatch.run(
        f"collective.{tag}",
        lambda: np.asarray(
            multihost_utils.process_allgather(value, tiled=tiled)))


def allgather_host_rows(n_unique: int, local_rows: "np.ndarray",
                        fill=0) -> "np.ndarray":
    """Exchange per-host strided-shard rows into the full row matrix.

    `local_rows` are this host's rows for `host_shard(range(n_unique))`
    in shard order; every host receives the identical (n_unique, ...)
    array. The one pad/process_allgather/strided-reassemble protocol
    shared by the sketching backends — the reassembly stride MUST
    mirror host_shard's `items[rank::count]`, so it lives next to it.
    """
    n_proc = process_count()
    if n_proc == 1:
        # Identity — and process_allgather's single-process return has
        # no leading process axis, so the reassembly below would
        # misindex it.
        return np.asarray(local_rows)[:n_unique]
    per = -(-n_unique // n_proc)
    padded = np.full((per, *local_rows.shape[1:]), fill,
                     dtype=local_rows.dtype)
    padded[: local_rows.shape[0]] = local_rows
    gathered = exchange("host-rows", padded)
    out = np.empty((n_unique, *local_rows.shape[1:]),
                   dtype=local_rows.dtype)
    for p in range(n_proc):
        idxs = np.arange(p, n_unique, n_proc)
        out[idxs] = gathered[p, : idxs.shape[0]]
    return out


def sharded_optional_floats(n_total: int, compute_mine,
                            owner=None) -> "List[Optional[float]]":
    """Distribute `n_total` Optional[float] computations across hosts.

    Every process calls this with the same n_total (a collective).
    `compute_mine(indices)` returns this host's values for its shard;
    `owner(k) -> int` assigns item k to a process (default: stride
    `k % P`) — callers pick owners so a shard shares expensive context
    (e.g. pair endpoints whose profiles the host already holds). The
    exchange carries explicit indices, so any deterministic ownership
    works. A host whose compute raises reports failure through the
    exchange and EVERY host re-raises — a lone crash never strands the
    peers inside the collective. None rides as NaN (producers never
    emit NaN values).
    """
    n_proc = process_count()
    if n_proc <= 1:
        return compute_mine(list(range(n_total)))
    rank = process_index()
    if owner is None:
        mine = list(range(rank, n_total, n_proc))
    else:
        mine = [k for k in range(n_total) if owner(k) % n_proc == rank]

    err: "Exception | None" = None
    vals: "List[Optional[float]]" = []
    try:
        vals = list(compute_mine(mine))
        if len(vals) != len(mine):
            raise RuntimeError(
                f"compute_mine returned {len(vals)} values for "
                f"{len(mine)} indices")
    except Exception as e:  # noqa: BLE001 - re-raised after exchange
        err = e
    raise_if_any_host_failed(err)

    sizes = exchange("shard-sizes",
                     np.array([len(mine)], dtype=np.int64))
    per = max(int(sizes.max()), 1)
    local = np.full((per, 2), np.nan, dtype=np.float64)
    local[:, 0] = -1.0  # "no item here"
    for r, (k, v) in enumerate(zip(mine, vals)):
        local[r, 0] = float(k)
        if v is not None:
            local[r, 1] = v
    gathered = exchange("shard-values", local)
    out: "List[Optional[float]]" = [None] * n_total
    for p in range(n_proc):
        for row in gathered[p]:
            k = int(row[0])
            if k >= 0:
                out[k] = None if np.isnan(row[1]) else float(row[1])
    return out


def raise_if_any_host_failed(err: "Exception | None") -> None:
    """Collective status exchange before a data collective: every
    process reports whether its local compute failed; if any did, ALL
    raise (the failing host its own error, peers a pointer to it) —
    a lone crash must never strand the other hosts inside the data
    exchange. Callers must reach this on every process."""
    if process_count() <= 1:
        if err is not None:
            raise err
        return
    status = np.array([1 if err is not None else 0], dtype=np.int64)
    statuses = exchange("host-status", status)
    if err is not None:
        raise err
    if int(statuses.sum()):
        raise RuntimeError(
            "a peer process failed its shard of a distributed pass; "
            "see that process's log for the original error")


def tokens_agree(token: bytes) -> bool:
    """True iff every process passed the identical token (fixed-length
    digest; callers hash variable-size state first). Used to make
    checkpoint resume all-or-nothing across hosts."""
    import hashlib

    digest = np.frombuffer(
        hashlib.sha256(token).digest(), dtype=np.uint8).copy()
    if process_count() == 1:
        return True
    gathered = exchange("resume-token", digest)
    return bool((gathered == gathered[0]).all())


def global_sketch_matrix(
    local_rows: np.ndarray,
    global_n: int,
    mesh: Mesh,
    axis_name: str = "i",
) -> jax.Array:
    """Assemble per-host sketch rows into one row-sharded global array.

    `local_rows` are this host's rows of the (global_n, K) matrix in
    host_shard order (strided); they are re-laid out into the
    contiguous row-sharded global array the pairwise kernels expect.
    No host ever materializes the full matrix: each host contributes
    exactly its rows via make_array_from_process_local_data, and the
    permutation from strided ingestion order to contiguous row order
    happens on device.

    global_n must be divisible by the mesh size (callers pad with
    SENTINEL rows, as the pairwise kernels already require).
    """
    n_proc = process_count()
    if global_n % mesh.devices.size:
        raise ValueError(
            f"global_n {global_n} not divisible by mesh size "
            f"{mesh.devices.size}; pad first")
    if n_proc == 1:
        sharding = NamedSharding(mesh, P(axis_name, None))
        return jax.device_put(local_rows, sharding)

    # Strided ingestion order -> contiguous global order: host p holds
    # global rows [p, p+P, p+2P, ...]. Build the global array in strided
    # order (host-contiguous blocks), then apply the inverse permutation
    # on device (an all-to-all XLA resolves from the sharding).
    sharding = NamedSharding(mesh, P(axis_name, None))
    strided = jax.make_array_from_process_local_data(
        sharding, local_rows, (global_n, local_rows.shape[1]))
    # row g of `strided` is global row (g % P) * ceil + ... : compute the
    # permutation explicitly instead: strided index s = p * per + q holds
    # global row q * P + p, where per = global_n // P.
    per = global_n // n_proc
    s_idx = np.arange(global_n)
    g_idx = (s_idx % per) * n_proc + (s_idx // per)
    inv = np.empty(global_n, dtype=np.int64)
    inv[g_idx] = s_idx

    @jax.jit
    def permute(x):
        out = jax.numpy.take(x, jax.numpy.asarray(inv), axis=0)
        return jax.lax.with_sharding_constraint(out, sharding)

    return permute(strided)
