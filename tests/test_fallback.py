"""No Mosaic kernel failure is swallowed on the default path.

Every device extraction offers a Mosaic kernel with an XLA twin, and the
Mosaic kernel is the default on a TPU backend. When that kernel fails,
the failure propagates — pinned or chosen by default — so a broken chip
path can never pass for a working one by quietly running its twin. Each
case makes the default pick the Mosaic path, breaks the kernel below
the former fallback gate, and expects the error.
"""

import numpy as np
import pytest

import jax

from galah_tpu.ops import fragment_ani, hll, pairwise, sketch_stream
from galah_tpu.ops import sparse_device


class _Boom(RuntimeError):
    pass


def _raise(*_a, **_k):
    raise _Boom("injected Mosaic failure")


def _raise_on_pallas(position):
    """A stand-in that fails only on the Mosaic path (use_pallas at
    `position` or as a keyword) and returns an empty result otherwise,
    so a fallback to the XLA twin would pass silently."""

    def fake(*args, **kwargs):
        pallas = kwargs.get("use_pallas", args[position]
                            if len(args) > position else False)
        if pallas:
            raise _Boom("injected Mosaic failure")
        return {}

    return fake


def _sorted_rows(n=16, width=32, seed=0):
    rng = np.random.default_rng(seed)
    mat = rng.integers(0, 1 << 62, size=(n, width), dtype=np.uint64)
    mat.sort(axis=1)
    return mat


def _threshold_pairs(monkeypatch):
    monkeypatch.setattr(pairwise, "_threshold_pairs_single",
                        _raise_on_pallas(6))
    pairwise.threshold_pairs(_sorted_rows(), k=21, min_ani=0.9,
                             row_tile=64)


def _screen_pairs(monkeypatch):
    monkeypatch.setattr(pairwise, "_screen_pairs_single",
                        _raise_on_pallas(6))
    pairwise.screen_pairs(_sorted_rows(), np.full(16, 32), 0.8,
                          row_tile=64)


def _hll_threshold_pairs(monkeypatch):
    monkeypatch.setattr(hll, "_hll_threshold_single",
                        _raise_on_pallas(5))
    regs = np.zeros((16, 64), dtype=np.uint8)
    hll.hll_threshold_pairs(regs, k=21, min_ani=0.9)


def _pairlist_blocked(monkeypatch):
    monkeypatch.setattr(sparse_device, "_batch_pair_stats",
                        _raise_on_pallas(4))
    pi = np.arange(8)
    sparse_device.pair_stats_for_pairs(_sorted_rows(), pi, pi + 1, 32,
                                       batch=4)


def _pairlist_gather(monkeypatch):
    monkeypatch.setenv("GALAH_TPU_PAIRLIST_STRATEGY", "gather")
    monkeypatch.setattr(sparse_device, "_gather_tile_stats", _raise)
    pi = np.arange(8)
    sparse_device.pair_stats_for_pairs(_sorted_rows(), pi, pi + 1, 32)


def _fragment(monkeypatch):
    monkeypatch.setattr(fragment_ani, "_resolve_fragment_strategy",
                        lambda *a, **k: ("pallas", False))
    monkeypatch.setattr(fragment_ani, "_directed_ani_batch_pallas",
                        _raise)
    fragment_ani.directed_ani_batch([(None, None)])


def _fused_sketch(monkeypatch):
    monkeypatch.setattr(sketch_stream, "_pack_fused",
                        lambda genomes: ([], []))
    monkeypatch.setattr(sketch_stream, "_sketch_packed_fused", _raise)

    class _G:
        codes = np.zeros(100, dtype=np.uint8)

    list(sketch_stream._iter_fused_sketches(
        iter([("g.fna", _G())]), 1000, 21, 0, "murmur3"))


SITES = {
    "threshold_pairs": _threshold_pairs,
    "screen_pairs": _screen_pairs,
    "hll_threshold_pairs": _hll_threshold_pairs,
    "pairlist_blocked": _pairlist_blocked,
    "pairlist_gather": _pairlist_gather,
    "fragment_window_hits": _fragment,
    "fused_sketch": _fused_sketch,
}


@pytest.mark.parametrize("site", sorted(SITES))
def test_default_mosaic_failure_propagates(site, monkeypatch):
    monkeypatch.setattr(hll, "use_pallas_default", lambda: True)
    # the single-device implementation: the test session's 8-device
    # CPU mesh would otherwise route the AUTO path to the sharded twins
    monkeypatch.setattr(jax, "device_count", lambda *a, **k: 1)
    with pytest.raises(_Boom):
        SITES[site](monkeypatch)
