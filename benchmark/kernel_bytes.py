"""Least bytes a kernel's work needs, counted from its shapes.

These are properties of the work, not of an implementation, so a later
kernel that moves more is measured against the same count.
"""

from __future__ import annotations


def fragment_membership_bytes(query_hashes: int,
                              reference_hashes: int) -> int:
    """One directed pair of the exact-ANI membership test: every query
    k-mer hash (8 B) and every distinct reference hash (8 B) read once,
    one flag byte per query hash written."""
    return 8 * query_hashes + 8 * reference_hashes + query_hashes
