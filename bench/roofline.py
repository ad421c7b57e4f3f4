"""Chip peaks and the operations and bytes a kernel call needs.

The peaks live in ``bench/peaks.json``, keyed by JAX's ``device_kind``; a
kind that is not there is an error, never a default.  The counts are the
algorithm's, computed from the call's shapes: padding and masked rows that
a kernel computes anyway are not counted, so they show as waste.
"""
from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parent / "peaks.json"


class UnknownDevice(KeyError):
    """The device kind has no entry in the peaks table."""


def peaks_for(kind: str) -> dict:
    table = json.loads(PEAKS.read_text())
    if kind not in table:
        raise UnknownDevice(f"no peaks for device kind {kind!r}; known: "
                            f"{sorted(table)}")
    return table[kind]


def quant_matmul_cost(m: int, k: int, n: int):
    """(operations, bytes) of one w8a8 matmul of ``m`` valid rows: int8
    activations and weight codes in, float32 outputs and per-column steps
    out, each read or written once."""
    return 2 * m * k * n, k * n + m * k + 4 * m * n + 4 * n


def least_seconds(ops: float, nbytes: float, peaks: dict) -> float:
    """The roofline bound of int8 work: the slower of compute and HBM."""
    return max(ops / peaks["int8_op_s"], nbytes / peaks["hbm_byte_s"])
