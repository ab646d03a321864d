"""The one traffic generator: reads a mix's data file and turns it, with
the run's seed, into the inputs of one run.

A mix file (``traffic/<name>.json``) names its ``kind`` and parameters:

* ``offline`` — a closed loop of back-to-back batches: ``batch`` images at
  ``resolution``, cycling ``distinct_batches`` batches made on the device
  from the seed; ``compare`` images of the window's logits, drawn from
  the seed, are compared with the reference.
"""

from __future__ import annotations

import json
from pathlib import Path

import numpy as np

KINDS = ("offline",)


def load(path: Path) -> dict:
    mix = json.loads(Path(path).read_text())
    if mix.get("kind") not in KINDS:
        raise ValueError(f"{path}: kind must be one of {KINDS}, "
                         f"got {mix.get('kind')!r}")
    return mix


def rng_for(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([seed % (1 << 64), stream])
