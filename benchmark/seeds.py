"""Sub-seeds of ``--seed``: any whole number, also past 2**31, gives
independent 31-bit streams by name, the same on every machine."""

from __future__ import annotations

import zlib

import numpy as np


def sub_seed(seed: int, name: str) -> int:
    """A 31-bit seed for the stream ``name`` of run seed ``seed``."""
    entropy = [int(seed) & 0xFFFFFFFF, int(seed) >> 32,
               zlib.crc32(name.encode())]
    return int(np.random.SeedSequence(entropy).generate_state(1)[0]
               & 0x7FFFFFFF)


def rng(seed: int, name: str) -> np.random.Generator:
    return np.random.default_rng(sub_seed(seed, name))
