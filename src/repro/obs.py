"""Named host spans and device scopes of the fit loops.

``span`` marks host time: a profiler ``TraceAnnotation`` on the host plane,
on the same clock as the device events; a few hundred ns when no profiler
runs. ``scope`` names the device ops traced inside it: a
``jax.named_scope``, which sets HLO op metadata (``op_name``) only, adds no
op and leaves fusion as it was. Eager ops take no scope (each primitive is
compiled once, without the name stack), so eager per-fit work carries a
span alone. Every name starts with ``kmeans.``, by which a trace reader
finds it.
"""
from __future__ import annotations

import jax

PREFIX = "kmeans."


def span(name: str) -> jax.profiler.TraceAnnotation:
    """Host span ``kmeans.<name>``."""
    return jax.profiler.TraceAnnotation(PREFIX + name)


def scope(name: str):
    """Device region ``kmeans.<name>`` of the ops traced inside it."""
    return jax.named_scope(PREFIX + name)
