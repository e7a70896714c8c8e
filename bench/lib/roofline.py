"""Bytes the WTBC descent has to read, counted from the requests alone.

A DR search pops segments; for every pop (and once for the whole collection
before the first pop) it counts each query word in a document range: a
rank at both ends of the range on every level of the word's codeword, so
``2 * cw_len`` rank probes per word.  Each probe reads one ``block``-byte
tile of its level and one 4-byte counter entry; that is what the index
layout fixes.  Padded beam lanes, the rows a batch is padded with, the rows
of a batch that finished early, the query-length padding and any larger
read a lowering makes (the TPU kernel's 8 KiB counter group, its levels past
the codeword's end) are not counted, so the count is the same whatever
implements the kernel, and the roofline share it gives is a lower bound.
"""
from __future__ import annotations

COUNTER_ENTRY_BYTES = 4
# the descent kernel's op in a TPU trace: the Pallas call's custom-call,
# named after the jitted ``_descend`` of kernels/wavelet_descent.py
DESCENT_OP = r"^%_descend(\.\d+)? = "


def probes(pops: int, cw_len_sum: int) -> int:
    """Rank probes of one request: ``pops`` plus the initial count, times
    two per codeword level of every query word."""
    return (int(pops) + 1) * 2 * int(cw_len_sum)


def probe_bytes(block: int) -> int:
    return int(block) + COUNTER_ENTRY_BYTES
