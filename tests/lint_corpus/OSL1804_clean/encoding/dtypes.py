"""Corpus mini contract registry (OSL1804 clean fixture): the registry,
the policy constants and the struct field sets all agree."""

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
