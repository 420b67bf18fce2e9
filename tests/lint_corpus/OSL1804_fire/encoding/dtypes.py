"""Corpus mini contract registry (OSL1804 fire fixture).

``node_valid`` is a field of the EncodedCluster next door but has no
ARENA_CONTRACTS entry here: a field added without its contract."""

import numpy as np

FLOAT_DTYPE = np.float32
INT_DTYPE = np.int32

AXIS_ALIASES = {
    "n_topo": "Tk",
}

ARENA_CONTRACTS = {
    "alloc": ("FLOAT_DTYPE", ("N", "R")),
    "node_domain": ("INT_DTYPE", ("N", "Tk")),
}

STATE_CONTRACTS = {
    "used": ("FLOAT_DTYPE", ("N", "R")),
}

KERNEL_ARG_CONTRACTS = {}

STRUCT_PARAM_NAMES = {}
