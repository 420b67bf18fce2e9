"""Corpus mini arena structs — ``node_valid`` has no registry entry."""

from typing import NamedTuple

import numpy as np


class EncodedCluster(NamedTuple):
    alloc: np.ndarray
    node_domain: np.ndarray
    node_valid: np.ndarray


class ScanState(NamedTuple):
    used: np.ndarray
