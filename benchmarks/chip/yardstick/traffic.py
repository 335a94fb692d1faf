"""The one traffic generator: turns a traffic mix's data file and a seed
into the exact requests of a run.

A mix (``benchmarks/chip/traffic/<name>.json``) names a ``route`` and a
``loop``.  The one route is ``register_build`` in a ``closed`` loop:
``clients`` callers, each registering a record under a new name and then
building its coreset, the next when the last one answered, until the
window closes.  The records are the configuration's ``recordcount``
records, drawn once from the mix's own ``record_seed``; the run's seed
draws only the order in which the clients insert them, over and over
under new names.  So every seed builds the same records, as many times
each give or take one, and a build's work (which depends on the record's
content) does not change from seed to seed.  Everything here is numpy:
the load generator process never imports JAX.
"""
from __future__ import annotations

import json
import pathlib

import numpy as np

from .signals import Record, record, seed_rng

__all__ = ["load", "insert_order", "insert_index", "record_at",
           "insert_name", "TAG_CHECK", "TAG_WARM"]

ROUTES = ("register_build",)

# seed-stream tags: every draw of a run comes from seed_rng(seed, TAG, ...)
TAG_RECORD, TAG_ORDER, TAG_CHECK, TAG_WARM = 5, 8, 6, 7


def load(path) -> dict:
    mix = json.loads(pathlib.Path(path).read_text())
    if mix.get("route") not in ROUTES:
        raise ValueError(f"{path}: route must be one of {ROUTES}")
    if mix.get("loop") != "closed":
        raise ValueError(f"{path}: loop must be closed")
    return mix


def insert_order(config: dict, seed: int) -> np.ndarray:
    """The configuration's record indices in the order this run inserts
    them."""
    return seed_rng(seed, TAG_ORDER).permutation(int(config["recordcount"]))


def insert_index(mix: dict, order, client: int, j: int) -> int:
    """Which record closed-loop client ``client`` inserts as its
    ``j``-th."""
    return int(order[(client + j * int(mix["clients"])) % len(order)])


def record_at(mix: dict, config: dict, i: int) -> Record:
    """The configuration's record ``i``, from the mix's record seed."""
    return record(config["signal"], f"record-{i}", int(mix["record_seed"]),
                  TAG_RECORD, i)


def insert_name(client: int, j: int) -> str:
    return f"fresh-{client}-{j}"
