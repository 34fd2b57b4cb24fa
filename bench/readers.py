"""Arithmetic the per-layer metric readers in ``bench/metrics/`` share.

Each reader gets the run's ``harness.Record`` and returns a number, or
``None`` when the run holds nothing for it to read (another traffic kind,
no trace); the harness then leaves the metric out of the result line.
"""
from __future__ import annotations

import statistics


def mfu(rec, kind: str):
    """Model FLOPs completed in the window over the window and the chips'
    bf16 peak, in percent. The program computes in float32, which the chip
    runs as several bf16 passes: the bf16 peak is the chip's highest."""
    if rec.kind != kind or not rec.peak or not rec.units:
        return None
    return 100.0 * rec.flops_per_unit * rec.units / (
        rec.window_s * rec.chips * rec.peak["bf16_flops"])


def idle_share(rec, kind: str):
    """Share of the traced window in which no operation ran on the device,
    in percent."""
    if rec.kind != kind or rec.trace is None:
        return None
    return 100.0 * (1.0 - rec.trace["busy_s"] / rec.trace["window_s"])


def host_ms(rec, kind: str, span: str):
    """Median over the occurrences of ``span`` of its duration less the
    device busy time inside it, in milliseconds."""
    if rec.kind != kind or rec.trace is None or not rec.trace["spans"].get(span):
        return None
    return 1e3 * statistics.median(d - b for d, b in rec.trace["spans"][span])
