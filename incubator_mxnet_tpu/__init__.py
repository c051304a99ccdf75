"""incubator_mxnet_tpu: a TPU-native deep learning framework with Apache
MXNet 2.0 capability parity, built on jax/XLA/pallas/pjit.

Typical use mirrors the reference:

    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import np, npx, autograd, gluon

    net = gluon.nn.Dense(10)
    net.initialize()
    with autograd.record():
        loss = net(np.ones((2, 4))).sum()
    loss.backward()
"""
from __future__ import annotations

__version__ = "0.1.0"

# Multi-process rendezvous must happen before anything touches the XLA
# backend; join from env at import time when a coordinator is configured
# (the reference's analogue: ps-lite rendezvous from DMLC_* env on
# `mx.kv.create('dist_*')`, SURVEY.md §3.5).
import os as _os

# Memory-reserve knob must be forwarded BEFORE anything can initialize the
# XLA backend (profiler autostart, dist rendezvous below) — once a client
# exists, XLA_PYTHON_CLIENT_MEM_FRACTION is read-only (SURVEY §5.6).
if _os.environ.get("MXNET_GPU_MEM_POOL_RESERVE") and \
        "XLA_PYTHON_CLIENT_MEM_FRACTION" not in _os.environ:
    try:
        _frac = max(0.0, min(
            1.0, 1.0 - float(_os.environ["MXNET_GPU_MEM_POOL_RESERVE"]) / 100.0))
        _os.environ["XLA_PYTHON_CLIENT_MEM_FRACTION"] = f"{_frac:.2f}"
    except ValueError:
        pass

# The persistent compile cache is keyed by its directory, so it is placed
# once, here, before anything below can compile (see _startup.py).
from ._startup import configure_compile_cache as _configure_compile_cache

_configure_compile_cache()

if _os.environ.get("COORDINATOR_ADDRESS") or _os.environ.get("DMLC_PS_ROOT_URI"):
    from .parallel import dist as _dist

    _dist.initialize()

from . import base  # noqa: F401
from .base import MXNetError  # noqa: F401
from .device import (  # noqa: F401
    Context,
    Device,
    cpu,
    current_device,
    gpu,
    gpu_memory_info,
    memory_stats,
    num_gpus,
    num_tpus,
    tpu,
)
from . import ndarray  # noqa: F401
from . import ndarray as nd  # noqa: F401
from .ndarray.ndarray import NDArray, waitall  # noqa: F401
from . import numpy  # noqa: F401
from . import numpy as np  # noqa: F401
from . import numpy_extension  # noqa: F401
from . import numpy_extension as npx  # noqa: F401
from . import autograd  # noqa: F401
from . import random  # noqa: F401
from . import initializer  # noqa: F401
from . import initializer as init  # noqa: F401
from . import optimizer  # noqa: F401
from . import gluon  # noqa: F401
from . import kvstore  # noqa: F401
from . import kvstore as kv  # noqa: F401
from . import library  # noqa: F401
from . import operator  # noqa: F401
from . import image  # noqa: F401
from . import recordio  # noqa: F401
from . import lr_scheduler  # noqa: F401
from . import amp  # noqa: F401
from . import io  # noqa: F401
from . import parallel  # noqa: F401
from . import profiler  # noqa: F401
from . import symbol  # noqa: F401
from . import symbol as sym  # noqa: F401
from . import name  # noqa: F401
from . import attribute  # noqa: F401
from .attribute import AttrScope  # noqa: F401
from . import runtime  # noqa: F401
from . import rtc  # noqa: F401
from . import partition  # noqa: F401
from . import remat  # noqa: F401
from . import preemption  # noqa: F401
from . import callback  # noqa: F401
from . import engine  # noqa: F401
from . import context  # noqa: F401
from . import executor  # noqa: F401
from . import dlpack  # noqa: F401
from . import libinfo  # noqa: F401
from . import registry  # noqa: F401
from . import model  # noqa: F401
from . import visualization  # noqa: F401
from . import visualization as viz  # noqa: F401
from . import error  # noqa: F401
from . import log  # noqa: F401
from . import util  # noqa: F401
from . import analysis  # noqa: F401
from . import telemetry  # noqa: F401
from . import fault  # noqa: F401
from . import serve  # noqa: F401

util._apply_env_config()  # honor MXNET_* knobs (SURVEY §5.6)
from . import test_utils  # noqa: F401
from . import contrib  # noqa: F401
