"""The work a classification needs, counted from a configuration's live
sizes, and the chip peaks it is measured against.

Operations per row are the two crossbars' multiply-adds, 2*K*n + 2*n*m,
on the live literal, clause and class counts, never on the padded
operand shapes a kernel happens to stream.  Bytes per call are the live
float32 fabric (K*n + n*m cells) read once, plus the int8 literal rows
sent.  A later change to padding or layout therefore cannot change the
count, and trimming dead work shows up as a higher share.
"""
from __future__ import annotations

import json
import pathlib

PEAKS_FILE = pathlib.Path(__file__).resolve().parent / "peaks.json"
CELL_BYTES = 4      # float32 read current per live cell
LITERAL_BYTES = 1   # int8 literal


def ops_per_row(cfg: dict) -> int:
    K, n, m = cfg["n_literals"], cfg["n_clauses"], cfg["n_classes"]
    return 2 * K * n + 2 * n * m


def fabric_bytes(cfg: dict) -> int:
    K, n, m = cfg["n_literals"], cfg["n_clauses"], cfg["n_classes"]
    return (K * n + n * m) * CELL_BYTES


def call_bytes(cfg: dict, rows: int) -> int:
    """Bytes one call must move: the fabric once, and its literal rows."""
    return fabric_bytes(cfg) + rows * cfg["n_literals"] * LITERAL_BYTES


def peaks(device_kind: str) -> dict:
    """Peak FLOP/s and HBM bytes/s of one chip; an unknown kind is an
    error, never a default."""
    table = json.loads(PEAKS_FILE.read_text())["devices"]
    if device_kind not in table:
        raise ValueError(f"no peaks for device kind {device_kind!r} in "
                         f"{PEAKS_FILE.name} (known: {sorted(table)})")
    return table[device_kind]


def bound_s(cfg: dict, rows_per_call, peak: dict) -> tuple[float, str]:
    """Least time the chip needs for calls of the given live row counts,
    each call bound by the slower of its operations and its bytes; and
    which side bounds most of it ("compute" or "memory")."""
    compute = memory = 0.0
    total = 0.0
    for rows in rows_per_call:
        c = rows * ops_per_row(cfg) / peak["flops_per_s"]
        m = call_bytes(cfg, rows) / peak["hbm_bytes_per_s"]
        total += max(c, m)
        compute += c if c >= m else 0.0
        memory += m if m > c else 0.0
    return total, ("compute" if compute > memory else "memory")
