"""Abstract-eval shape-contract harness (GL5xx).

``jax.eval_shape`` traces every registered op over a dtype x
shape-quantum lattice on CPU — no compilation, no device — and diffs
the resulting output signatures against the committed snapshot
(``shape_contracts.json`` next to this module). A kernel signature
regression (an output dtype widened, a padding change leaking into the
public shape, an op that stops accepting a lattice point) fails tier-1
without any hardware:

  GL501  an op's output signature differs from the committed snapshot
  GL502  lattice drift: a computed case missing from the snapshot, a
         stale snapshot entry, or an op that now raises at trace time

Regenerate the snapshot after an *intentional* contract change with
``python -m galah_tpu.analysis --update-snapshots``.

The lattice points sit deliberately ON and OFF the TPU tiling quanta
(K = 128 vs 1000, pair counts 8 vs 9) so ragged-input padding behavior
is part of the pinned contract.
"""

from __future__ import annotations

import json
import os
from typing import Callable, Dict, List, Tuple

from galah_tpu.analysis.core import Finding, Severity

SNAPSHOT_PATH = os.path.join(os.path.dirname(__file__),
                             "shape_contracts.json")


def _sig(x) -> str:
    return f"{x.dtype}[{','.join(str(d) for d in x.shape)}]"


def _flatten_sig(out) -> str:
    import jax

    leaves = jax.tree_util.tree_leaves(out)
    return ", ".join(_sig(leaf) for leaf in leaves)


def _lattice() -> List[Tuple[str, str, Callable[[], object],
                             Tuple[object, ...], Dict[str, object]]]:
    """(op_name, case_key, fn_getter, args, kwargs) rows.

    fn_getter defers the ops import so building the lattice never pays
    for jax; args are ShapeDtypeStructs (eval_shape consumes abstract
    values only).
    """
    import jax
    import jax.numpy as jnp

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype)

    u64, f32 = jnp.uint64, jnp.float32
    rows: List[Tuple[str, str, Callable[[], object],
                     Tuple[object, ...], Dict[str, object]]] = []

    def add(op_name, case, getter, *args, **kwargs):
        rows.append((op_name, case, getter, args, kwargs))

    def get(module, attr):
        def getter():
            import importlib

            return getattr(importlib.import_module(module), attr)
        return getter

    tile_stats = get("galah_tpu.ops.pairwise", "tile_stats")
    tile_ani = get("galah_tpu.ops.pairwise", "tile_ani")
    tile_icount = get("galah_tpu.ops.pairwise", "tile_intersect_counts")
    tile_pallas = get("galah_tpu.ops.pallas_pairwise",
                      "tile_stats_pallas")
    tile_ipallas = get("galah_tpu.ops.pallas_pairwise",
                       "tile_intersect_pallas")
    pairlist = get("galah_tpu.ops.pallas_pairlist",
                   "pair_stats_pairs_pallas")
    murmur = get("galah_tpu.ops.pallas_sketch", "murmur3_k21_pallas")
    hll_tile = get("galah_tpu.ops.pallas_hll", "hll_union_stats_tile")
    hll_xla = get("galah_tpu.ops.hll", "_xla_union_stats")
    hll_card = get("galah_tpu.ops.hll", "hll_cardinality")

    # XLA pairwise tiles: the production sketch width, on- and
    # off-quantum (these trace in milliseconds)
    for br, bc, k in ((8, 128, 1000), (1, 1, 128), (16, 256, 1024)):
        case = f"br={br},bc={bc},K={k},uint64"
        add("pairwise.tile_stats", case, tile_stats,
            sds((br, k), u64), sds((bc, k), u64),
            sketch_size=k, k=21)
        add("pairwise.tile_ani", case, tile_ani,
            sds((br, k), u64), sds((bc, k), u64),
            sketch_size=k, k=21)
        add("pairwise.tile_intersect_counts", case, tile_icount,
            sds((br, k), u64), sds((bc, k), u64))

    # 2D-mesh tile wrapper: same stats as pairwise.tile_stats but with
    # the int32 output contract the lattice assembler depends on (no
    # new Pallas kernel — the 2D path reuses the 1-D tile kernels, so
    # Mosaic coverage is inherited from the rows above)
    tile2d = get("galah_tpu.parallel.mesh", "tile2d_stats")
    for br, bc, k in ((8, 128, 1000), (16, 256, 1024)):
        add("mesh.tile2d_stats", f"br={br},bc={bc},K={k},uint64",
            tile2d, sds((br, k), u64), sds((bc, k), u64),
            sketch_size=k, k=21)

    # Mosaic pairwise tiles: tracing cost scales with the unrolled
    # chunk count (~25 s at K=1000), so the lattice pins padding
    # behavior at small widths — on-quantum, off-quantum (K=200 pads
    # to 256; br/bc pad to the program/lane quanta)
    for br, bc, k in ((1, 1, 128), (4, 4, 200), (8, 16, 256)):
        case = f"br={br},bc={bc},K={k},uint64"
        add("pallas_pairwise.tile_stats_pallas", case, tile_pallas,
            sds((br, k), u64), sds((bc, k), u64), sketch_size=k)
        add("pallas_pairwise.tile_intersect_pallas", case, tile_ipallas,
            sds((br, k), u64), sds((bc, k), u64))

    # blocked pairlist kernel: ragged and block-aligned pair counts,
    # pinned block_pairs so the env flag cannot skew the contract
    for b, k in ((1, 128), (8, 136), (9, 136)):
        add("pallas_pairlist.pair_stats_pairs_pallas",
            f"B={b},K={k},P=8,uint64", pairlist,
            sds((b, k), u64), sds((b, k), u64),
            sketch_size=k, block_pairs=8)

    # fragment window-match kernel: job-bucket x span-bucket lattice
    # points at the production geometry (8 sublanes x 128 lanes per
    # job, int32 hi/lo planes — the u64 split happens on the host, so
    # the device boundary is 32-bit by construction; int32 per-job row
    # plans; int8 flags out)
    fragment = get("galah_tpu.ops.pallas_fragment", "_window_hits_jit")
    i32 = jnp.int32
    for jobs, span in ((8, 1), (8, 2), (16, 4)):
        add("pallas_fragment._window_hits_jit",
            f"jobs={jobs},span={span},int32", fragment,
            sds((jobs,), i32), sds((jobs,), i32),
            sds((jobs * 8, 128), i32), sds((jobs * 8, 128), i32),
            sds((jobs * span * 8, 128), i32),
            sds((jobs * span * 8, 128), i32),
            span=span, interpret=True)

    # quarantined murmur3 kernel keeps its boundary contract pinned too
    for n in (1, 1000, 65536):
        add("pallas_sketch.murmur3_k21_pallas", f"n={n},uint64",
            murmur, sds((n,), u64), sds((n,), u64), sds((n,), u64))

    # fused hash+bottom-k sketch kernel: job-bucket x span-bucket
    # lattice at the (BLOCK_SUB x LANES)-block geometry, both hash
    # algorithms (murmur3 ships 3 key words per position, tpufast 1);
    # output is the (jobs, R_REG, CAND_SUB * LANES) candidate file
    fused = get("galah_tpu.ops.pallas_sketch", "fused_sketch_candidates")
    _fb = 512 * 128  # BLOCK_SUB * LANES positions per kernel block
    for jobs, span, algo, n_words in ((8, 1, "murmur3", 3),
                                      (8, 2, "murmur3", 3),
                                      (16, 1, "tpufast", 1)):
        w = span * _fb
        add("pallas_sketch.fused_sketch_candidates",
            f"jobs={jobs},span={span},{algo},uint64", fused,
            tuple(sds((jobs, w), u64) for _ in range(n_words)),
            sds((jobs, w), jnp.bool_),
            algo=algo, seed=0, interpret=True)

    # HLL union tiles: Mosaic kernel and its XLA fallback twin must
    # keep identical signatures
    for br, bc, m in ((8, 8, 1024), (64, 128, 4096)):
        case = f"br={br},bc={bc},m={m},float32"
        add("pallas_hll.hll_union_stats_tile", case, hll_tile,
            sds((br, m), f32), sds((bc, m), f32), chunk=1024)
        add("hll._xla_union_stats", case, hll_xla,
            sds((br, m), f32), sds((bc, m), f32))
    add("hll.hll_cardinality", "m=4096,uint8", hll_card,
        sds((4096,), jnp.uint8))

    # greedy-selection window fold + membership argmax (f64 by
    # contract — the NaN >= thr comparison must match the host's
    # None-guarded float64 compare bit-for-bit); pow2 buckets from
    # greedy_select._bucket, bool flags alongside
    wsel = get("galah_tpu.ops.greedy_select", "_window_select_jit")
    margmax = get("galah_tpu.ops.greedy_select", "_membership_argmax_jit")
    f64, b8 = jnp.float64, jnp.bool_
    for w in (8, 64):
        add("greedy_select._window_select_jit", f"W={w},float64",
            wsel, sds((w, w), f64), sds((w,), b8), sds((w,), b8),
            sds((), f64))
    for gg, rr in ((8, 8), (64, 16)):
        add("greedy_select._membership_argmax_jit",
            f"G={gg},R={rr},float64", margmax, sds((gg, rr), f64))
    return rows


def compute_contracts() -> Tuple[Dict[str, Dict[str, str]],
                                 List[Finding]]:
    """op -> case -> output signature, tracing each lattice point."""
    import functools

    import jax

    findings: List[Finding] = []
    out: Dict[str, Dict[str, str]] = {}
    for op_name, case, getter, args, kwargs in _lattice():
        try:
            fn = getter()
            result = jax.eval_shape(functools.partial(fn, **kwargs),
                                    *args)
            out.setdefault(op_name, {})[case] = _flatten_sig(result)
        except Exception as e:  # noqa: BLE001 - reported as a finding
            findings.append(Finding(
                "GL502", Severity.ERROR, "galah_tpu/analysis/shapes.py",
                0,
                f"{op_name}[{case}] failed abstract eval: "
                f"{type(e).__name__}: {str(e).splitlines()[0] if str(e) else ''}",
                op_name))
    return out, findings


def load_snapshot() -> Dict[str, Dict[str, str]]:
    if not os.path.isfile(SNAPSHOT_PATH):
        return {}
    with open(SNAPSHOT_PATH, "r", encoding="utf-8") as fh:
        return json.load(fh).get("contracts", {})


def write_snapshot(contracts: Dict[str, Dict[str, str]]) -> None:
    with open(SNAPSHOT_PATH, "w", encoding="utf-8") as fh:
        json.dump({"version": 1, "contracts": contracts}, fh, indent=1,
                  sort_keys=True)
        fh.write("\n")


def _verdict_digest() -> str:
    """Content digest over everything the lattice outcome depends on:
    the op modules the lattice traces, this file (the lattice itself),
    the committed snapshot, and the jax version. Any edit to any of
    them changes the digest, so a cached verdict can never go stale —
    it can only be missed and recomputed."""
    import hashlib

    h = hashlib.sha256()
    try:
        import jax

        h.update(jax.__version__.encode())
    except Exception:  # noqa: BLE001 - no jax, no cached verdict reuse
        h.update(b"no-jax")
    pkg = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    paths = [os.path.abspath(__file__), SNAPSHOT_PATH,
             os.path.join(pkg, "parallel", "mesh.py")]
    ops_dir = os.path.join(pkg, "ops")
    for base, _dirs, names in sorted(os.walk(ops_dir)):
        paths.extend(os.path.join(base, n) for n in sorted(names)
                     if n.endswith(".py"))
    for p in paths:
        h.update(os.path.basename(p).encode())
        try:
            with open(p, "rb") as fh:
                h.update(fh.read())
        except OSError:
            h.update(b"<absent>")
    return h.hexdigest()


def check_shape_contracts(cache_dir: str = None) -> List[Finding]:
    """GL501/GL502: computed lattice vs committed snapshot.

    With ``cache_dir`` the verdict (the finding list itself) is cached
    keyed by a digest over the op sources + lattice + snapshot + jax
    version — this family costs ~12 s of jax tracing per run, so a
    warm hit is what makes a cached ``galah-tpu lint`` fast."""
    cache = None
    if cache_dir:
        from galah_tpu.analysis.ir import IRCache

        cache = IRCache(cache_dir)
        digest = _verdict_digest()
        hit = cache.load_verdict("shapes", digest)
        if hit is not None:
            return [Finding(code, Severity[sev], path, line, msg, sym)
                    for code, sev, path, line, msg, sym
                    in hit["findings"]]
    findings = _check_shape_contracts_cold()
    if cache is not None:
        cache.store_verdict("shapes", digest, {
            "findings": [[f.code, f.severity.name, f.path, f.line,
                          f.message, f.symbol] for f in findings]})
    return findings


def _check_shape_contracts_cold() -> List[Finding]:
    computed, findings = compute_contracts()
    snapshot = load_snapshot()
    rel = "galah_tpu/analysis/shape_contracts.json"
    if not snapshot:
        findings.append(Finding(
            "GL502", Severity.ERROR, rel, 0,
            "no committed shape-contract snapshot; run "
            "`python -m galah_tpu.analysis --update-snapshots`", ""))
        return findings
    for op_name, cases in sorted(computed.items()):
        snap_cases = snapshot.get(op_name)
        if snap_cases is None:
            findings.append(Finding(
                "GL502", Severity.ERROR, rel, 0,
                f"op {op_name} missing from the snapshot "
                "(--update-snapshots after an intentional change)",
                op_name))
            continue
        for case, sig in sorted(cases.items()):
            want = snap_cases.get(case)
            if want is None:
                findings.append(Finding(
                    "GL502", Severity.ERROR, rel, 0,
                    f"{op_name}[{case}] missing from the snapshot",
                    op_name))
            elif want != sig:
                findings.append(Finding(
                    "GL501", Severity.ERROR, rel, 0,
                    f"{op_name}[{case}] signature changed: snapshot "
                    f"{want!r} vs computed {sig!r}", op_name))
        for case in sorted(set(snap_cases) - set(cases)):
            findings.append(Finding(
                "GL502", Severity.ERROR, rel, 0,
                f"{op_name}[{case}] is in the snapshot but no longer "
                "in the lattice (stale entry)", op_name))
    for op_name in sorted(set(snapshot) - set(computed)):
        findings.append(Finding(
            "GL502", Severity.ERROR, rel, 0,
            f"snapshot op {op_name} is no longer registered in the "
            "lattice (stale entry)", op_name))
    return findings
