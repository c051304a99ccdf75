"""`mx.npx` — numpy-extension namespace: the NN operator corpus.

Reference: `python/mxnet/numpy_extension/` + kernels under `src/operator/nn/`
(Convolution, FullyConnected, BatchNorm, Pooling, softmax family, Dropout —
see SURVEY.md §2.3). TPU-native design notes:

- every op lowers to jax/lax primitives so XLA tiles matmuls/convs onto the
  MXU and fuses the elementwise epilogues (the role oneDNN/cuDNN fusion plays
  in the reference, `src/operator/subgraph/dnnl/`);
- ops that mutate auxiliary state (BatchNorm running stats — FMutateInputs in
  the reference) funnel through `utils.trace.register_aux_update` so they
  functionalize correctly under jit;
- dropout/random ops draw from the global RNG (`random.next_key`), which
  remains fresh under jit tracing (traced key + fold-in counter).
"""
from __future__ import annotations

import math

import numpy as onp

from .. import autograd
from ..base import np_dtype
from ..ndarray.ndarray import NDArray, apply_op, apply_op_flat
from ..random import next_key
from ..utils.trace import register_aux_update

__all__ = [
    "activation", "relu", "sigmoid", "softmax", "log_softmax", "masked_softmax",
    "masked_log_softmax", "leaky_relu", "fully_connected", "convolution",
    "deconvolution", "pooling", "batch_norm", "layer_norm", "group_norm",
    "residual_dropout_ln",
    "instance_norm", "l2_normalization", "dropout", "embedding", "one_hot",
    "pick", "topk", "batch_dot", "flash_attention", "sharding_constraint",
    "gather_nd", "scatter_nd", "sequence_mask",
    "sequence_last", "sequence_reverse", "rnn", "erf", "erfinv", "gamma",
    "gammaln", "digamma", "cast", "reshape", "arange_like", "shape_array",
    "stop_gradient", "foreach", "while_loop", "cond", "set_np", "reset_np",
    "is_np_array", "is_np_shape", "waitall", "load", "save", "seed",
    "gelu", "smooth_l1", "clip_global_norm",
    "box_iou", "box_nms", "box_encode", "box_decode", "bipartite_matching",
    "roi_align", "slice_like", "broadcast_like", "batch_take",
    # contrib corpus (_contrib_misc / _transformer)
    "quadratic", "index_copy", "index_array", "gradientmultiplier",
    "dynamic_reshape", "count_sketch", "hawkesll", "round_ste", "sign_ste",
    "all_finite", "multi_all_finite", "ctc_loss", "adaptive_avg_pooling2d",
    "bilinear_resize2d", "batch_norm_with_relu", "sync_batch_norm",
    "softsign", "pad", "norm", "slice", "slice_channel", "add_n",
    "interleaved_matmul_selfatt_qk", "interleaved_matmul_selfatt_valatt",
    "interleaved_matmul_encdec_qk", "interleaved_matmul_encdec_valatt",
    "div_sqrt_dim", "sldwin_atten_score", "sldwin_atten_context",
    "sldwin_atten_mask_like",
]


from ._boxes import (  # noqa: F401
    batch_take, bipartite_matching, box_decode, box_encode, box_iou,
    box_nms, broadcast_like, multibox_detection, multibox_prior,
    multibox_target, roi_align, slice_like,
)
from ._contrib_misc import (  # noqa: F401
    adaptive_avg_pooling2d, add_n, all_finite, batch_norm_with_relu,
    bilinear_resize2d, count_sketch, ctc_loss, dynamic_reshape,
    gradientmultiplier, hawkesll, index_array, index_copy,
    multi_all_finite, norm, pad, quadratic, round_ste, sign_ste,
    slice, slice_channel, softsign, sync_batch_norm,
)
from ._detection import (  # noqa: F401
    deformable_psroi_pooling, mrcnn_mask_target, multi_proposal,
    proposal, psroi_pooling, rroi_align,
)
from ._graph import (  # noqa: F401
    dgl_adjacency, dgl_csr_neighbor_non_uniform_sample,
    dgl_csr_neighbor_uniform_sample, dgl_graph_compact, dgl_subgraph,
    edge_id, getnnz,
)
from ._spatial import (  # noqa: F401
    bilinear_sampler, correlation, deformable_convolution, fft,
    grid_generator, ifft, modulated_deformable_convolution, roi_pooling,
    spatial_transformer,
)
from ._transformer import (  # noqa: F401
    div_sqrt_dim, interleaved_matmul_encdec_qk,
    interleaved_matmul_encdec_valatt, interleaved_matmul_selfatt_qk,
    interleaved_matmul_selfatt_valatt, sldwin_atten_context,
    sldwin_atten_mask_like, sldwin_atten_score,
)


def __getattr__(name):
    if name == "Custom":  # lazy: operator.py imports back into this package
        from ..operator import Custom

        return Custom
    if name == "image":
        # npx.image = the op namespace (to_tensor/normalize/resize/...,
        # reference `src/operator/image/`) PLUS the imperative augmenter
        # classes re-exported for back-compat (`mx.image`)
        import importlib
        import types

        from .. import image as _imperative

        # importlib (not `from . import image`): the relative import form
        # re-enters this __getattr__ and recurses
        _ops = importlib.import_module(
            "incubator_mxnet_tpu.numpy_extension.image")

        mod = types.ModuleType("incubator_mxnet_tpu.npx.image")
        for src in (_imperative, _ops):
            for n in dir(src):
                if not n.startswith("_"):
                    setattr(mod, n, getattr(src, n))
        globals()["image"] = mod          # cache: resolve once
        return mod
    raise AttributeError(f"module 'npx' has no attribute {name!r}")


def _safe_accumulation():
    """MXNET_SAFE_ACCUMULATION=1 → fp32 accumulation for low-precision
    inputs in softmax/norm reductions (reference env_var.md; matmul
    accumulation is fp32 on the MXU regardless)."""
    import os

    return os.environ.get("MXNET_SAFE_ACCUMULATION") == "1"


def _jnp():
    import jax.numpy as jnp

    return jnp


def _lax():
    import jax.lax as lax

    return lax


def _tuple(x, n):
    if x is None:
        return (1,) * n
    if isinstance(x, int):
        return (x,) * n
    return tuple(x)


# ---------------------------------------------------------------------------
# activations / softmax family
# ---------------------------------------------------------------------------

def relu(data):
    return apply_op("relu", lambda x: _jnp().maximum(x, 0), (data,))


def sigmoid(data):
    import jax

    return apply_op("sigmoid", jax.nn.sigmoid, (data,))


def gelu(data, approximate=True):
    import jax

    return apply_op("gelu", lambda x: jax.nn.gelu(x, approximate=approximate), (data,))


def activation(data, act_type="relu", **kwargs):  # noqa: ARG001
    import jax

    fns = {
        "relu": lambda x: _jnp().maximum(x, 0),
        "sigmoid": jax.nn.sigmoid,
        "tanh": _jnp().tanh,
        "softrelu": jax.nn.softplus,
        "softsign": lambda x: x / (1 + _jnp().abs(x)),
        "log_sigmoid": jax.nn.log_sigmoid,
        "mish": lambda x: x * _jnp().tanh(jax.nn.softplus(x)),
        "gelu": jax.nn.gelu,
        "silu": jax.nn.silu,
    }
    if act_type not in fns:
        raise ValueError(f"unknown activation {act_type!r}")
    return apply_op(f"activation.{act_type}", fns[act_type], (data,))


def leaky_relu(data, gamma=None, act_type="leaky", slope=0.25,
               lower_bound=0.125, upper_bound=0.334, **kwargs):  # noqa: ARG001
    import jax

    jnp = _jnp()
    if act_type == "leaky":
        return apply_op("leaky_relu", lambda x: jnp.where(x >= 0, x, slope * x), (data,))
    if act_type == "elu":
        return apply_op("elu", lambda x: jax.nn.elu(x, alpha=slope), (data,))
    if act_type == "selu":
        return apply_op("selu", jax.nn.selu, (data,))
    if act_type == "gelu":
        return apply_op("gelu", lambda x: jax.nn.gelu(x, approximate=False), (data,))
    if act_type == "prelu":
        def f(x, g):
            g2 = g.reshape((1, -1) + (1,) * (x.ndim - 2)) if g.ndim == 1 and x.ndim > 2 else g
            return jnp.where(x >= 0, x, g2 * x)

        return apply_op("prelu", f, (data, gamma))
    if act_type == "rrelu":
        if autograd.is_training():
            import jax.random as jr

            def f(x):
                u = jr.uniform(next_key(), x.shape, minval=lower_bound,
                               maxval=upper_bound)
                return jnp.where(x >= 0, x, u * x)

            return apply_op("rrelu", f, (data,))
        mid = (lower_bound + upper_bound) / 2.0
        return apply_op("rrelu", lambda x: jnp.where(x >= 0, x, mid * x), (data,))
    raise ValueError(f"unknown leaky_relu act_type {act_type!r}")


def softmax(data, axis=-1, length=None, temperature=None, use_length=False,
            dtype=None, **kwargs):  # noqa: ARG001
    import jax

    jnp = _jnp()
    safe = _safe_accumulation()

    def f(x, ln):
        in_dt = x.dtype
        if safe and str(in_dt) in ("float16", "bfloat16"):
            # MXNET_SAFE_ACCUMULATION: reduce in fp32 (reference
            # softmax.cc AType promotion), cast back unless dtype= says
            # otherwise
            x = x.astype("float32")
        if temperature is not None and temperature != 1.0:
            x = x / temperature
        if ln is not None:
            idx = jnp.arange(x.shape[axis])
            shape = [1] * x.ndim
            shape[axis] = -1
            mask = idx.reshape(shape) < jnp.expand_dims(ln, axis=axis)
            x = jnp.where(mask, x, -jnp.inf)
            out = jax.nn.softmax(x, axis=axis)
            out = jnp.where(mask, out, 0.0)
        else:
            out = jax.nn.softmax(x, axis=axis)
        if dtype:
            return out.astype(np_dtype(dtype))
        return out.astype(in_dt) if safe else out

    ln = length if (use_length or length is not None) else None
    return apply_op("softmax", f,
                    (data, ln) if ln is not None else (data, None),
                    static_info={"axis": axis})


def batch_flatten(data, **kwargs):  # noqa: ARG001
    """Collapse all non-batch dims to 2-D (reference `Flatten` op,
    `src/operator/tensor/matrix_op.cc` — output (batch, -1))."""
    return apply_op("batch_flatten",
                    lambda x: x.reshape(x.shape[0], -1), (data,))


def softmin(data, axis=-1, temperature=None, dtype=None, **kwargs):  # noqa: ARG001
    """softmax of the negated input (reference: `src/operator/nn/softmax.cc`
    softmin registration)."""
    import jax

    def f(x):
        if temperature is not None and temperature != 1.0:
            x = x / temperature
        out = jax.nn.softmax(-x, axis=axis)
        return out.astype(np_dtype(dtype)) if dtype else out

    return apply_op("softmin", f, (data,))


def reshape_like(lhs, rhs, lhs_begin=None, lhs_end=None, rhs_begin=None,
                 rhs_end=None, **kwargs):  # noqa: ARG001
    """Reshape lhs to rhs's shape (reference:
    `src/operator/tensor/elemwise_unary_op_basic.cc` reshape_like).
    The range form replaces lhs.shape[lhs_begin:lhs_end] with
    rhs.shape[rhs_begin:rhs_end] (reference ReshapeLikeParam)."""
    lshape = tuple((lhs._data if hasattr(lhs, "_data") else lhs).shape)
    rshape = tuple((rhs._data if hasattr(rhs, "_data") else rhs).shape)
    lb = 0 if lhs_begin is None else lhs_begin
    le = len(lshape) if lhs_end is None else lhs_end
    rb = 0 if rhs_begin is None else rhs_begin
    re_ = len(rshape) if rhs_end is None else rhs_end
    lb += len(lshape) if lb < 0 else 0
    le += len(lshape) if le < 0 else 0
    rb += len(rshape) if rb < 0 else 0
    re_ += len(rshape) if re_ < 0 else 0
    shape = lshape[:lb] + rshape[rb:re_] + lshape[le:]
    import math

    if math.prod(shape) != math.prod(lshape):
        raise ValueError(
            f"reshape_like: target shape {shape} has "
            f"{math.prod(shape)} elements, lhs has {math.prod(lshape)}")
    return apply_op("reshape_like", lambda x: x.reshape(shape), (lhs,))


def log_softmax(data, axis=-1, temperature=None, dtype=None, **kwargs):  # noqa: ARG001
    import jax

    def f(x):
        if temperature is not None and temperature != 1.0:
            x = x / temperature
        out = jax.nn.log_softmax(x, axis=axis)
        return out.astype(np_dtype(dtype)) if dtype else out

    return apply_op("log_softmax", f, (data,))


def masked_softmax(data, mask=None, axis=-1, temperature=1.0, **kwargs):  # noqa: ARG001
    import jax

    jnp = _jnp()

    def f(x, m):
        if temperature != 1.0:
            x = x / temperature
        if m is not None:
            x = jnp.where(m.astype(bool), x, -jnp.inf)
        out = jax.nn.softmax(x, axis=axis)
        if m is not None:
            out = jnp.where(m.astype(bool), out, 0.0)
        return out

    return apply_op("masked_softmax", f, (data, mask))


def masked_log_softmax(data, mask=None, axis=-1, temperature=1.0):
    import jax

    jnp = _jnp()

    def f(x, m):
        if temperature != 1.0:
            x = x / temperature
        if m is not None:
            x = jnp.where(m.astype(bool), x, -jnp.inf)
        return jax.nn.log_softmax(x, axis=axis)

    return apply_op("masked_log_softmax", f, (data, mask))


# ---------------------------------------------------------------------------
# dense / conv / pooling  (the MXU path)
# ---------------------------------------------------------------------------

def fully_connected(x, weight, bias=None, num_hidden=None, no_bias=False,
                    flatten=True, **kwargs):  # noqa: ARG001
    jnp = _jnp()

    def f(x, w, b):
        from ..amp import amp_active, cast_for_matmul

        if flatten and x.ndim > 2:
            x = x.reshape(x.shape[0], -1)
        if amp_active():
            x, w = cast_for_matmul(x, w)
        y = jnp.matmul(x, w.T) if not flatten or x.ndim <= 2 else x @ w.T
        if b is not None:
            y = y + b.astype(y.dtype)
        return y

    if no_bias or bias is None:
        return apply_op("fully_connected", lambda x, w: f(x, w, None), (x, weight))
    return apply_op("fully_connected", f, (x, weight, bias))


def _conv_dn(ndim, layout):
    if layout is None:
        layout = {1: "NCW", 2: "NCHW", 3: "NCDHW"}[ndim]
    kernel_layout = {"NCW": "OIW", "NCHW": "OIHW", "NCDHW": "OIDHW",
                     "NWC": "WIO", "NHWC": "HWIO", "NDHWC": "DHWIO"}[layout]
    return layout, kernel_layout


def convolution(data, weight, bias=None, kernel=None, stride=None, dilate=None,
                pad=None, num_filter=None, num_group=1, no_bias=False,
                layout=None, **kwargs):  # noqa: ARG001
    lax = _lax()
    ndim = len(kernel) if kernel is not None else data.ndim - 2
    stride = _tuple(stride, ndim)
    dilate = _tuple(dilate, ndim)
    pad = _tuple(pad, ndim) if pad is not None else (0,) * ndim
    lhs_l, rhs_l = _conv_dn(ndim, layout)

    def f(x, w, b):
        from ..amp import amp_active, cast_for_matmul

        if amp_active():
            x, w = cast_for_matmul(x, w)
        y = lax.conv_general_dilated(
            x, w, window_strides=stride,
            padding=[(p, p) for p in pad],
            rhs_dilation=dilate,
            dimension_numbers=(lhs_l, rhs_l, lhs_l),
            feature_group_count=num_group,
            preferred_element_type=None,
        )
        if b is not None:
            c_axis = lhs_l.index("C")
            shape = [1] * y.ndim
            shape[c_axis] = -1
            y = y + b.reshape(shape).astype(y.dtype)
        return y

    if no_bias or bias is None:
        return apply_op("convolution", lambda x, w: f(x, w, None), (data, weight))
    return apply_op("convolution", f, (data, weight, bias))


def deconvolution(data, weight, bias=None, kernel=None, stride=None, dilate=None,
                  pad=None, adj=None, num_filter=None, num_group=1, no_bias=False,
                  layout=None, target_shape=None, **kwargs):  # noqa: ARG001
    lax = _lax()
    ndim = len(kernel) if kernel is not None else data.ndim - 2
    stride = _tuple(stride, ndim)
    dilate = _tuple(dilate, ndim)
    pad = _tuple(pad, ndim) if pad is not None else (0,) * ndim
    lhs_l, rhs_l = _conv_dn(ndim, layout)

    def f(x, w, b):
        # transposed conv: weight stored as (in, out/g, *k) in the reference
        y = lax.conv_transpose(
            x, w, strides=stride,
            padding=[(d * (k - 1) - p, d * (k - 1) - p)
                     for k, p, d in zip(kernel, pad, dilate)],
            rhs_dilation=dilate,
            dimension_numbers=(lhs_l, rhs_l.replace("O", "X").replace("I", "O").replace("X", "I"), lhs_l),
            transpose_kernel=True,
        )
        if b is not None:
            c_axis = lhs_l.index("C")
            shape = [1] * y.ndim
            shape[c_axis] = -1
            y = y + b.reshape(shape)
        return y

    if no_bias or bias is None:
        return apply_op("deconvolution", lambda x, w: f(x, w, None), (data, weight))
    return apply_op("deconvolution", f, (data, weight, bias))


def pooling(data, kernel=None, stride=None, pad=None, pool_type="max",
            global_pool=False, layout=None, count_include_pad=True,
            pooling_convention="valid", **kwargs):  # noqa: ARG001
    jnp = _jnp()
    lax = _lax()
    ndim = data.ndim - 2
    lhs_l, _ = _conv_dn(ndim, layout)
    spatial_axes = tuple(i for i, c in enumerate(lhs_l) if c not in ("N", "C"))

    if global_pool:
        red = {"max": jnp.max, "avg": jnp.mean, "sum": jnp.sum,
               "lp": lambda x, axis, keepdims: jnp.power(
                   jnp.sum(jnp.power(jnp.abs(x), 2), axis=axis, keepdims=keepdims), 0.5)}
        fn = red[pool_type]
        return apply_op("global_pool",
                        lambda x: fn(x, axis=spatial_axes, keepdims=True), (data,))

    kernel = _tuple(kernel, ndim)
    stride = _tuple(stride, ndim)
    pad = _tuple(pad, ndim) if pad is not None else (0,) * ndim
    window = [1] * data.ndim
    strides = [1] * data.ndim
    padding = [(0, 0)] * data.ndim
    for ax, k, s, p in zip(spatial_axes, kernel, stride, pad):
        window[ax] = k
        strides[ax] = s
        padding[ax] = (p, p)

    def _pad_for(x):
        # 'full' = ceil-mode output shape (reference PoolingParam
        # pooling_convention, `src/operator/nn/pooling-inl.h`): extend the
        # high-side padding so a partial final window is still emitted
        if pooling_convention != "full":
            return padding
        padl = list(padding)
        for ax, k, s, p in zip(spatial_axes, kernel, stride, pad):
            span = x.shape[ax] + 2 * p - k
            rem = span % s
            if rem:
                lo, hi = padl[ax]
                padl[ax] = (lo, hi + (s - rem))
        return tuple(padl)

    if pool_type == "max":
        def f(x):
            # integer identity for int inputs (int8 requantize chains pool
            # their CODES — max commutes with the monotone quantization)
            init = (-jnp.inf if jnp.issubdtype(x.dtype, jnp.floating)
                    else jnp.asarray(jnp.iinfo(x.dtype).min, x.dtype))
            return lax.reduce_window(x, init, lax.max, tuple(window),
                                     tuple(strides), _pad_for(x))
    elif pool_type in ("avg", "sum"):
        def f(x):
            pads = _pad_for(x)
            s = lax.reduce_window(x, 0.0, lax.add, tuple(window),
                                  tuple(strides), pads)
            if pool_type == "sum":
                return s
            if count_include_pad and pooling_convention != "full":
                return s / float(onp.prod(kernel))
            ones = jnp.ones(x.shape, x.dtype)
            if count_include_pad:
                # 'full' + include_pad: the reference divides a partial
                # final window by its size CLIPPED to height+pad
                # (pool.h hend=min(hstart+k, height+pad)), so pad cells
                # count but the ceil-extension does not — pre-pad the
                # ones with the REAL padding and reduce with only the
                # ceil extension as window padding
                np_pad = [(0, 0)] * x.ndim
                extra = [(0, 0)] * x.ndim
                for ax, (lo, hi) in enumerate(pads):
                    rl, rh = padding[ax]
                    np_pad[ax] = (rl, rh)
                    extra[ax] = (lo - rl, hi - rh)
                ones = jnp.pad(ones, np_pad, constant_values=1)
                cnt = lax.reduce_window(ones, 0.0, lax.add, tuple(window),
                                        tuple(strides), tuple(extra))
            else:
                cnt = lax.reduce_window(ones, 0.0, lax.add, tuple(window),
                                        tuple(strides), pads)
            return s / cnt
    else:
        raise ValueError(f"unsupported pool_type {pool_type!r}")
    return apply_op(f"pooling.{pool_type}", f, (data,))


# ---------------------------------------------------------------------------
# normalization
# ---------------------------------------------------------------------------

def batch_norm(x, gamma, beta, running_mean, running_var, eps=1e-5,
               momentum=0.9, fix_gamma=True, use_global_stats=False,
               output_mean_var=False, axis=1, **kwargs):  # noqa: ARG001
    jnp = _jnp()
    training = autograd.is_training() and not use_global_stats
    reduce_axes = tuple(i for i in range(x.ndim) if i != axis)
    shape = [1] * x.ndim
    shape[axis] = -1

    if training:
        def f(xv, g, b, rm, rv):
            mean = jnp.mean(xv, axis=reduce_axes)
            var = jnp.var(xv, axis=reduce_axes)
            gg = jnp.ones_like(g) if fix_gamma else g
            inv = gg * (1.0 / jnp.sqrt(var + eps))
            out = (xv - mean.reshape(shape)) * inv.reshape(shape) + b.reshape(shape)
            return out, mean, var

        out, bmean, bvar = apply_op("batch_norm", f,
                                    (x, gamma, beta, running_mean, running_var),
                                    n_outputs=3)
        # running-stat update (FMutateInputs semantics), functionalized under jit
        m = momentum
        register_aux_update(running_mean,
                            running_mean._data * m + bmean._data * (1 - m))
        register_aux_update(running_var,
                            running_var._data * m + bvar._data * (1 - m))
        if output_mean_var:
            return out, bmean, bvar
        return out

    def f(xv, g, b, rm, rv):
        gg = jnp.ones_like(g) if fix_gamma else g
        inv = gg * (1.0 / jnp.sqrt(rv + eps))
        return (xv - rm.reshape(shape)) * inv.reshape(shape) + b.reshape(shape)

    out = apply_op("batch_norm", f, (x, gamma, beta, running_mean, running_var))
    if output_mean_var:
        return out, running_mean, running_var
    return out


def _placed_on_cpu(a):
    """True when an EAGER jax array is committed to cpu devices (the
    check_consistency cpu leg on a chip host); tracers follow the
    process default backend."""
    try:
        return all(d.platform == "cpu" for d in a.devices())
    except Exception:
        return False


def layer_norm(data, gamma=None, beta=None, axis=-1, eps=1e-5, **kwargs):  # noqa: ARG001
    jnp = _jnp()

    from ..ops import _dispatch

    if gamma is not None and beta is not None:
        from ..ops import layer_norm as _ln

        xv = data._data if isinstance(data, NDArray) else data
        if (_dispatch.use_pallas() and not _placed_on_cpu(xv)
                and _ln.supports(xv.shape, axis, xv.shape[-1])
                and jnp.issubdtype(xv.dtype, jnp.floating)):
            # fused pallas path: one HBM pass fwd, fused bwd with row-stat
            # residuals (see ops/layer_norm.py)
            _dispatch.note("layer_norm", "pallas")
            return apply_op(
                "layer_norm",
                lambda x, g, b: _ln.layer_norm(x, g, b, eps=eps),
                (data, gamma, beta))
    _dispatch.note("layer_norm", "xla")

    def f(x, g, b):
        # dtype-preserving with f32 internal math: the statistics and the
        # normalize are always computed in float32 (the reference's
        # FP32_FUNCS discipline), but the output is written back in the
        # input dtype — under bf16 AMP this halves LN HBM traffic, which
        # profiling shows dominates the op (the math itself is free)
        import jax as _jax

        xd = x.dtype
        x = x.astype(jnp.float32)
        mean = jnp.mean(x, axis=axis, keepdims=True)
        var = jnp.var(x, axis=axis, keepdims=True)
        out = (x - mean) * _jax.lax.rsqrt(var + eps)
        if g is not None:
            g = g.astype(jnp.float32)
            out = out * jnp.expand_dims(g, tuple(i for i in range(x.ndim)
                                                 if i != (axis % x.ndim))) \
                if g.ndim == 1 and x.ndim > 1 else out * g
        if b is not None:
            b = b.astype(jnp.float32)
            out = out + (jnp.expand_dims(b, tuple(i for i in range(x.ndim)
                                                  if i != (axis % x.ndim)))
                         if b.ndim == 1 and x.ndim > 1 else b)
        return out.astype(xd)

    return apply_op("layer_norm", f, (data, gamma, beta))


def group_norm(data, gamma=None, beta=None, num_groups=1, eps=1e-5, **kwargs):  # noqa: ARG001
    jnp = _jnp()

    def f(x, g, b):
        n, c = x.shape[0], x.shape[1]
        rest = x.shape[2:]
        xg = x.reshape((n, num_groups, c // num_groups) + rest)
        axes = tuple(range(2, xg.ndim))
        mean = jnp.mean(xg, axis=axes, keepdims=True)
        var = jnp.var(xg, axis=axes, keepdims=True)
        out = ((xg - mean) / jnp.sqrt(var + eps)).reshape(x.shape)
        shape = [1, c] + [1] * len(rest)
        if g is not None:
            out = out * g.reshape(shape)
        if b is not None:
            out = out + b.reshape(shape)
        return out

    return apply_op("group_norm", f, (data, gamma, beta))


def instance_norm(data, gamma=None, beta=None, eps=1e-5, **kwargs):  # noqa: ARG001
    jnp = _jnp()

    def f(x, g, b):
        axes = tuple(range(2, x.ndim))
        mean = jnp.mean(x, axis=axes, keepdims=True)
        var = jnp.var(x, axis=axes, keepdims=True)
        out = (x - mean) / jnp.sqrt(var + eps)
        shape = [1, x.shape[1]] + [1] * (x.ndim - 2)
        if g is not None:
            out = out * g.reshape(shape)
        if b is not None:
            out = out + b.reshape(shape)
        return out

    return apply_op("instance_norm", f, (data, gamma, beta))


def l2_normalization(data, eps=1e-10, mode="instance"):
    jnp = _jnp()

    def f(x):
        if mode == "instance":
            axes = tuple(range(1, x.ndim))
        elif mode == "channel":
            axes = (1,)
        elif mode == "spatial":
            axes = tuple(range(2, x.ndim))
        else:
            raise ValueError(mode)
        norm = jnp.sqrt(jnp.sum(x * x, axis=axes, keepdims=True) + eps)
        return x / norm

    return apply_op("l2_normalization", f, (data,))


# ---------------------------------------------------------------------------
# dropout / embedding / indexing helpers
# ---------------------------------------------------------------------------

def dropout(data, p=0.5, axes=(), mode="training", **kwargs):  # noqa: ARG001
    jnp = _jnp()
    apply = (mode == "always") or autograd.is_training()
    if not apply or p == 0:
        return data if isinstance(data, NDArray) else NDArray(data)
    import jax.random as jr

    from ..ops import dropout as _hw

    key = next_key()
    dshape = tuple((data._data if isinstance(data, NDArray) else data).shape)
    ddtype = (data._data if isinstance(data, NDArray) else data).dtype
    from ..ops import _dispatch

    if _hw.supports(dshape, axes, ddtype, p) and _hw.use_kernel(key):
        # hardware-RNG pallas kernel: rescues the threefry-keyed path from
        # VPU bit-gen cost (see ops/dropout.py `use_kernel` for the
        # measured dispatch policy)
        def f(x):
            return _hw.dropout(x, key, p)

        _dispatch.note("dropout", "pallas")
        return apply_op("dropout", f, (data,))
    _dispatch.note("dropout", "xla")

    def f(x):
        shape = list(x.shape)
        if axes:
            for ax in axes:
                shape[ax] = 1
        keep = jr.bernoulli(key, 1.0 - p, tuple(shape))
        return jnp.where(keep, x / (1.0 - p), 0.0)

    return apply_op("dropout", f, (data,))


def embedding(data, weight, input_dim=None, output_dim=None, dtype=None,
              sparse_grad=False, **kwargs):  # noqa: ARG001
    jnp = _jnp()

    def f(idx, w):
        out = jnp.take(w, idx.astype(jnp.int32), axis=0)
        return out.astype(np_dtype(dtype)) if dtype else out

    from ..ndarray.ndarray import _is_tracer

    if not sparse_grad or _is_tracer(getattr(data, "_data", data)) \
            or _is_tracer(weight._data):
        # dense path; under a hybridize/jit trace XLA's scatter-add IS the
        # efficient embedding gradient, so sparse bookkeeping is eager-only
        return apply_op("embedding", f, (data, weight))

    # sparse_grad=True (reference: EmbeddingOp row_sparse gradient,
    # `src/operator/tensor/indexing_op.cc`): custom tape node whose backward
    # emits a RowSparseNDArray cotangent for `weight` — only the looked-up
    # rows are stored, never a (vocab, dim) dense buffer.
    from .. import autograd as _ag
    from ..autograd import TapeNode
    from ..ndarray.ndarray import _ShapeDtype
    from ..ndarray.sparse import RowSparseNDArray

    idx_val = data._data if isinstance(data, NDArray) else jnp.asarray(data)
    w_arr = weight
    out = NDArray(f(idx_val, w_arr._data))

    if _ag.is_recording() and (w_arr._node is not None
                               or w_arr._grad is not None):
        w_shape = tuple(w_arr.shape)

        def vjp_fn(cot):
            cot = cot[0] if isinstance(cot, tuple) else cot
            flat_idx = idx_val.reshape(-1).astype(jnp.int32)
            flat_cot = cot.reshape(-1, cot.shape[-1])
            return (None,
                    RowSparseNDArray(flat_cot, flat_idx, w_shape))

        node = TapeNode(None, [idx_val, w_arr._data],
                        [data if isinstance(data, NDArray) else NDArray(idx_val),
                         w_arr],
                        1, "embedding_sparse", vjp_fn=vjp_fn)
        node.out_avals = [_ShapeDtype(out._data)]
        node.tuple_out = False
        out._node = node
        out._out_idx = 0
    return out


def one_hot(data, depth, on_value=1.0, off_value=0.0, dtype="float32"):
    import jax

    def f(idx):
        oh = jax.nn.one_hot(idx.astype("int32"), depth, dtype=np_dtype(dtype))
        return oh * (on_value - off_value) + off_value

    return apply_op("one_hot", f, (data,))


def pick(data, index, axis=-1, mode="clip", keepdims=False):
    jnp = _jnp()

    def f(x, idx):
        idx = idx.astype(jnp.int32)
        if mode == "clip":
            idx = jnp.clip(idx, 0, x.shape[axis] - 1)
        else:
            idx = idx % x.shape[axis]
        out = jnp.take_along_axis(x, jnp.expand_dims(idx, axis=axis), axis=axis)
        return out if keepdims else jnp.squeeze(out, axis=axis)

    return apply_op("pick", f, (data, index))


def topk(data, axis=-1, k=1, ret_typ="indices", is_ascend=False, dtype="float32"):
    jnp = _jnp()
    lax = _lax()

    def f(x):
        xm = jnp.moveaxis(x, axis, -1)
        vals, idx = lax.top_k(-xm if is_ascend else xm, k)
        if is_ascend:
            vals = -vals
        vals = jnp.moveaxis(vals, -1, axis)
        idx = jnp.moveaxis(idx, -1, axis)
        if ret_typ == "value":
            return vals
        if ret_typ == "indices":
            return idx.astype(np_dtype(dtype))
        if ret_typ == "both":
            return vals, idx.astype(np_dtype(dtype))
        if ret_typ == "mask":
            m = jnp.zeros(xm.shape, dtype=np_dtype(dtype))
            m = m.at[..., idx].set(1)  # approximate
            return jnp.moveaxis(m, -1, axis)
        raise ValueError(ret_typ)

    n_outputs = 2 if ret_typ == "both" else 1
    return apply_op("topk", f, (data,), n_outputs=n_outputs)


def flash_attention(query, key, value, valid_length=None, causal=False,
                    sm_scale=None, layout="bhtd"):
    """Fused memory-linear attention — the pallas kernel in
    `ops/flash_attention.py` (reference role:
    `src/operator/subgraph/dnnl/dnnl_transformer_qk_property.h`).

    `layout`: "bhtd" for (B, H, T, D) tensors, "bthd" for (B, T, H, D) —
    the fused-qkv projection layout; passing it directly avoids
    materializing head transposes on the XLA path.
    `valid_length`: (B,) valid sequence lengths (replaces a dense mask).
    Differentiable (flash backward kernels via custom_vjp)."""
    from ..ops.flash_attention import flash_attention as _flash

    if valid_length is None:
        return apply_op(
            "flash_attention",
            lambda q, k, v: _flash(q, k, v, causal=causal, sm_scale=sm_scale,
                                   layout=layout),
            (query, key, value))
    return apply_op(
        "flash_attention",
        lambda q, k, v, vl: _flash(q, k, v, lengths=vl, causal=causal,
                                   sm_scale=sm_scale, layout=layout),
        (query, key, value, valid_length))


def residual_dropout_ln(x, h, gamma, beta, p=0.0, eps=1e-5, axis=-1):
    """``layer_norm(x + dropout_p(h))`` — the post-LN transformer residual
    site, fused into ONE pallas pass on TPU (`ops/fused_block.py`; 24
    such sites in BERT-base cost ~45 ms/step unfused at seq 512). Off
    TPU, or for layouts `ops.fused_block.supports` does not admit, the
    composed ops run instead with identical semantics."""
    import jax as _jax

    from .. import autograd
    from ..ops import _dispatch
    from ..ops import fused_block as _fb

    jnp = _jnp()
    p_eff = float(p) if autograd.is_training() else 0.0
    xv = x._data if isinstance(x, NDArray) else x
    hv = h._data if isinstance(h, NDArray) else h
    ndim = len(xv.shape)
    if (_dispatch.use_pallas() and axis in (-1, ndim - 1)
            and not _placed_on_cpu(xv)
            and _fb.supports(xv.shape, xv.shape[-1])
            and tuple(xv.shape) == tuple(hv.shape)  # kernel can't broadcast
            and p_eff < 1.0                         # p=1: composed path
            and jnp.issubdtype(xv.dtype, jnp.floating)):
        if p_eff > 0:
            key = next_key()
            raw = _jax.random.key_data(key) if jnp.issubdtype(
                getattr(key, "dtype", None), _jax.dtypes.prng_key) else key
            seeds = raw.reshape(-1)[:2].astype(jnp.int32)
        else:
            # no key consumed when nothing is random — keeps seeded runs
            # bit-identical with the composed fallback (which also draws
            # none) across backends and across eval passes
            seeds = jnp.zeros((2,), jnp.int32)

        def f(xa, ha, g, b, s):
            return _fb.residual_dropout_ln(xa, ha, g, b, p_eff, s, eps=eps)

        _dispatch.note("residual_dropout_ln", "pallas")
        return apply_op("residual_dropout_ln", f,
                        (x, h, gamma, beta, NDArray(seeds)))
    _dispatch.note("residual_dropout_ln", "composed")
    d = dropout(h, p=p) if p else h
    return layer_norm(x + d, gamma, beta, axis=axis, eps=eps)


def gelu_dropout(data, p=0.0, impl="auto"):
    """``dropout_p(gelu(x))``.

    impl="auto"/"xla": the composed ops — measured FASTEST on TPU when
    the input is a matmul output (XLA fuses gelu+mask into the matmul
    epilogue, so a pallas kernel boundary here COSTS ~2 ms/step on
    BERT-base: it forces the 402 MB hidden activation to materialize).
    impl="pallas": the in-VMEM-RNG kernel (`ops/fused_block.py`
    gelu_dropout) for call sites where the input is NOT epilogue-fusable
    (e.g. already materialized by a collective or a concat)."""
    import jax as _jax

    from .. import autograd
    from ..ops import _dispatch
    from ..ops import fused_block as _fb

    jnp = _jnp()
    p_eff = float(p) if autograd.is_training() else 0.0
    xv = data._data if isinstance(data, NDArray) else data
    if (impl == "pallas" and _dispatch.use_pallas()
            and 0 < p_eff < 1.0 and not _placed_on_cpu(xv)
            and len(xv.shape) >= 2 and xv.shape[-1] % 128 == 0
            and jnp.issubdtype(xv.dtype, jnp.floating)):
        key = next_key()
        raw = _jax.random.key_data(key) if jnp.issubdtype(
            getattr(key, "dtype", None), _jax.dtypes.prng_key) else key
        seeds = raw.reshape(-1)[:2].astype(jnp.int32)

        def f(u, s):
            return _fb.gelu_dropout(u, p_eff, s)

        _dispatch.note("gelu_dropout", "pallas")
        return apply_op("gelu_dropout", f, (data, NDArray(seeds)))
    _dispatch.note("gelu_dropout", "xla")
    out = gelu(data, approximate=False)
    return dropout(out, p=p) if p else out


def sharding_constraint(data, spec):
    """Annotate an activation with a mesh sharding (sequence/tensor parallel
    layout hints inside a traced step). Identity when no mesh is active or
    when executing eagerly — the constraint only matters under jit where
    GSPMD propagates it. Axes not present in the active mesh are dropped,
    so model code can name 'sp'/'tp' axes unconditionally."""
    import jax

    from ..parallel.mesh import current_mesh

    mesh = current_mesh()
    if mesh is None:
        return data
    P = jax.sharding.PartitionSpec
    spec = spec if isinstance(spec, P) else P(*spec)
    names = set(mesh.axis_names)

    def _clean(axis):
        if axis is None:
            return None
        if isinstance(axis, (list, tuple)):
            kept = [a for a in axis if a in names]
            return tuple(kept) if kept else None
        return axis if axis in names else None

    cleaned = P(*[_clean(a) for a in spec])
    sharding = jax.sharding.NamedSharding(mesh, cleaned)

    def f(x):
        if not isinstance(x, jax.core.Tracer):
            return x  # eager: placement is the runtime's business
        return jax.lax.with_sharding_constraint(x, sharding)

    return apply_op("sharding_constraint", f, (data,))


def batch_dot(a, b, transpose_a=False, transpose_b=False, **kwargs):  # noqa: ARG001
    jnp = _jnp()

    def f(x, y):
        from ..amp import amp_active, cast_for_matmul

        if amp_active():
            x, y = cast_for_matmul(x, y)
        if transpose_a:
            x = jnp.swapaxes(x, -1, -2)
        if transpose_b:
            y = jnp.swapaxes(y, -1, -2)
        return jnp.matmul(x, y)

    # transpose flags ride in the eqn name so partition-backend guards
    # (e.g. flash attention's QK-stage check) can see them — shapes alone
    # cannot distinguish q@k^T from q@k when k is square (r3 ADVICE)
    return apply_op("batch_dot", f, (a, b),
                    static_info={"transpose_a": bool(transpose_a),
                                 "transpose_b": bool(transpose_b)})


def gather_nd(data, indices):
    jnp = _jnp()

    def f(x, idx):
        idx = idx.astype(jnp.int32)
        return x[tuple(idx[i] for i in range(idx.shape[0]))]

    return apply_op("gather_nd", f, (data, indices))


def scatter_nd(data, indices, shape):
    jnp = _jnp()

    def f(d, idx):
        idx = idx.astype(jnp.int32)
        out = jnp.zeros(shape, d.dtype)
        return out.at[tuple(idx[i] for i in range(idx.shape[0]))].add(d)

    return apply_op("scatter_nd", f, (data, indices))


# ---------------------------------------------------------------------------
# sequence ops (reference: src/operator/sequence_*.cc)
# ---------------------------------------------------------------------------

def sequence_mask(data, sequence_length=None, use_sequence_length=False,
                  value=0.0, axis=0):
    jnp = _jnp()
    if not use_sequence_length or sequence_length is None:
        return data if isinstance(data, NDArray) else NDArray(data)

    def f(x, ln):
        steps = jnp.arange(x.shape[axis])
        batch_axis = 1 - axis  # sequence ops are (T, N, ...) or (N, T, ...)
        shape = [1] * x.ndim
        shape[axis] = -1
        steps = steps.reshape(shape)
        lshape = [1] * x.ndim
        lshape[batch_axis] = -1
        mask = steps < ln.reshape(lshape)
        return jnp.where(mask, x, value)

    return apply_op("sequence_mask", f, (data, sequence_length))


def sequence_last(data, sequence_length=None, use_sequence_length=False, axis=0):
    jnp = _jnp()

    def f(x, ln):
        if ln is None:
            return jnp.take(x, -1, axis=axis)
        idx = (ln - 1).astype(jnp.int32)
        return jnp.take_along_axis(
            x, idx.reshape((1, -1) + (1,) * (x.ndim - 2)) if axis == 0
            else idx.reshape((-1, 1) + (1,) * (x.ndim - 2)),
            axis=axis).squeeze(axis)

    ln = sequence_length if use_sequence_length else None
    return apply_op("sequence_last", f, (data, ln))


def sequence_reverse(data, sequence_length=None, use_sequence_length=False, axis=0):
    jnp = _jnp()

    def f(x, ln):
        if ln is None:
            return jnp.flip(x, axis=axis)
        T = x.shape[axis]
        steps = jnp.arange(T)
        ln_i = ln.astype(jnp.int32)
        # reversed index within each valid prefix, identity beyond
        rev = jnp.where(steps[None, :] < ln_i[:, None],
                        ln_i[:, None] - 1 - steps[None, :], steps[None, :])
        # data is (T, N, ...): gather along time per batch
        xm = jnp.moveaxis(x, axis, 0)
        out = jnp.take_along_axis(
            xm, jnp.moveaxis(rev, -1, 0).reshape((T, -1) + (1,) * (xm.ndim - 2)),
            axis=0)
        return jnp.moveaxis(out, 0, axis)

    ln = sequence_length if use_sequence_length else None
    return apply_op("sequence_reverse", f, (data, ln))


# ---------------------------------------------------------------------------
# fused RNN (reference: src/operator/rnn.cc:296 — LSTM/GRU/vanilla over a
# packed parameter vector). TPU design: lax.scan over time, weights unpacked
# from the flat vector with cuDNN-compatible gate order (LSTM: i f g o,
# GRU: r z n), so checkpoints trained on the reference load bit-compatibly.
# ---------------------------------------------------------------------------

def _rnn_gates(mode):
    return {"rnn_relu": 1, "rnn_tanh": 1, "lstm": 4, "gru": 3}[mode]


def _unpack_rnn_params(params, mode, num_layers, input_size, state_size, bidirectional,
                       projection_size=None):  # noqa: ARG001
    jnp = _jnp()
    ngates = _rnn_gates(mode)
    dirs = 2 if bidirectional else 1
    layers = []
    pos = 0
    for layer in range(num_layers):
        lsize = input_size if layer == 0 else state_size * dirs
        for _ in range(dirs):
            w_i2h = _lax().dynamic_slice(params, (pos,), (ngates * state_size * lsize,)) \
                .reshape(ngates * state_size, lsize)
            pos += ngates * state_size * lsize
            w_h2h = _lax().dynamic_slice(params, (pos,), (ngates * state_size * state_size,)) \
                .reshape(ngates * state_size, state_size)
            pos += ngates * state_size * state_size
            layers.append([w_i2h, w_h2h, None, None])
    idx = 0
    for layer in range(num_layers):
        for _ in range(dirs):
            b_i2h = _lax().dynamic_slice(params, (pos,), (ngates * state_size,))
            pos += ngates * state_size
            b_h2h = _lax().dynamic_slice(params, (pos,), (ngates * state_size,))
            pos += ngates * state_size
            layers[idx][2] = b_i2h
            layers[idx][3] = b_h2h
            idx += 1
    del jnp
    return layers


def rnn_param_size(mode, num_layers, input_size, state_size, bidirectional=False):
    ngates = _rnn_gates(mode)
    dirs = 2 if bidirectional else 1
    total = 0
    for layer in range(num_layers):
        lsize = input_size if layer == 0 else state_size * dirs
        total += dirs * ngates * state_size * (lsize + state_size + 2)
    return total


def _cell_step(mode, x_t, h, c, w_i2h, w_h2h, b_i2h, b_h2h):
    import jax

    jnp = _jnp()
    gates = x_t @ w_i2h.T + b_i2h + h @ w_h2h.T + b_h2h
    H = h.shape[-1]
    if mode == "lstm":
        i, f, g, o = (gates[..., :H], gates[..., H:2 * H], gates[..., 2 * H:3 * H],
                      gates[..., 3 * H:])
        i, f, o = jax.nn.sigmoid(i), jax.nn.sigmoid(f), jax.nn.sigmoid(o)
        g = jnp.tanh(g)
        c_new = f * c + i * g
        h_new = o * jnp.tanh(c_new)
        return h_new, c_new
    if mode == "gru":
        # cuDNN-style gru: r, z from combined; n uses r * (h W_hn + b_hn)
        xr, xz, xn = jnp.split(x_t @ w_i2h.T + b_i2h, 3, axis=-1)
        hr, hz, hn = jnp.split(h @ w_h2h.T + b_h2h, 3, axis=-1)
        r = jax.nn.sigmoid(xr + hr)
        z = jax.nn.sigmoid(xz + hz)
        n = jnp.tanh(xn + r * hn)
        h_new = (1 - z) * n + z * h
        return h_new, c
    act = jnp.tanh if mode == "rnn_tanh" else (lambda v: jnp.maximum(v, 0))
    h_new = act(gates)
    return h_new, c


def rnn(data=None, parameters=None, state=None, state_cell=None, mode="lstm",
        state_size=None, num_layers=1, bidirectional=False, p=0.0,
        state_outputs=False, projection_size=None, lstm_state_clip_min=None,
        lstm_state_clip_max=None, sequence_length=None, use_sequence_length=False,
        **kwargs):  # noqa: ARG001
    """Fused multi-layer RNN over time-major input (T, N, C)."""
    import jax

    jnp = _jnp()
    lax = _lax()
    dirs = 2 if bidirectional else 1
    input_size = data.shape[-1]

    dropout_keys = [next_key() for _ in range(max(0, num_layers - 1))] if p > 0 else []

    def f(x, params, h0, c0):
        layers = _unpack_rnn_params(params, mode, num_layers, input_size,
                                    state_size, bidirectional)
        out = x
        h_finals, c_finals = [], []
        for layer in range(num_layers):
            layer_outs = []
            for d in range(dirs):
                li = layer * dirs + d
                w_i2h, w_h2h, b_i2h, b_h2h = layers[li]
                h_init = h0[li]
                c_init = c0[li] if c0 is not None else jnp.zeros_like(h_init)
                seq = out if d == 0 else jnp.flip(out, axis=0)

                def step(carry, x_t, _w_i2h=w_i2h, _w_h2h=w_h2h, _b_i2h=b_i2h,
                         _b_h2h=b_h2h):
                    h, c = carry
                    h2, c2 = _cell_step(mode, x_t, h, c, _w_i2h, _w_h2h, _b_i2h,
                                        _b_h2h)
                    if mode == "lstm" and lstm_state_clip_min is not None:
                        c2 = jnp.clip(c2, lstm_state_clip_min, lstm_state_clip_max)
                    return (h2, c2), h2

                (h_f, c_f), ys = lax.scan(step, (h_init, c_init), seq)
                if d == 1:
                    ys = jnp.flip(ys, axis=0)
                layer_outs.append(ys)
                h_finals.append(h_f)
                c_finals.append(c_f)
            out = layer_outs[0] if dirs == 1 else jnp.concatenate(layer_outs, axis=-1)
            if p > 0 and layer < num_layers - 1:
                keep = jax.random.bernoulli(dropout_keys[layer], 1.0 - p, out.shape) \
                    if autograd.is_training() else None
                if keep is not None:
                    out = jnp.where(keep, out / (1.0 - p), 0.0)
        h_out = jnp.stack(h_finals, axis=0)
        if mode == "lstm":
            c_out = jnp.stack(c_finals, axis=0)
            return out, h_out, c_out
        return out, h_out

    n_outputs = 3 if mode == "lstm" else 2
    outs = apply_op("rnn", f, (data, parameters, state, state_cell),
                    n_outputs=n_outputs)
    if state_outputs:
        return outs
    return outs[0]


# ---------------------------------------------------------------------------
# scalar special functions
# ---------------------------------------------------------------------------

def erf(data):
    import jax

    return apply_op("erf", jax.scipy.special.erf, (data,))


def erfinv(data):
    import jax

    return apply_op("erfinv", jax.scipy.special.erfinv, (data,))


def gamma(data):
    import jax

    return apply_op("gamma", lambda x: _jnp().exp(jax.scipy.special.gammaln(x)), (data,))


def gammaln(data):
    import jax

    return apply_op("gammaln", jax.scipy.special.gammaln, (data,))


def digamma(data):
    import jax

    return apply_op("digamma", jax.scipy.special.digamma, (data,))


def smooth_l1(data, scalar=1.0):
    jnp = _jnp()
    s2 = scalar * scalar

    def f(x):
        return jnp.where(jnp.abs(x) < 1.0 / s2, 0.5 * s2 * x * x,
                         jnp.abs(x) - 0.5 / s2)

    return apply_op("smooth_l1", f, (data,))


# ---------------------------------------------------------------------------
# shape utilities
# ---------------------------------------------------------------------------

def cast(data, dtype):
    return data.astype(dtype)


def reshape(data, newshape, reverse=False, **kwargs):  # noqa: ARG001
    """npx.reshape with MXNet magic codes (-2 copy rest, -3 merge two,
    -4 split, -5 merge all remaining, -6 split into two)."""
    shape = list(newshape) if isinstance(newshape, (list, tuple)) else [newshape]
    in_shape = list(data.shape)
    if all(isinstance(s, int) and s >= -1 for s in shape):
        # handle 0 = copy input dim (MXNet legacy reshape semantic)
        out = [in_shape[i] if s == 0 and i < len(in_shape) else s
               for i, s in enumerate(shape)]
        return data.reshape(tuple(out))
    out = []
    i = 0
    it = iter(range(len(shape)))
    for si in it:
        s = shape[si]
        if s == -2:
            out.extend(in_shape[i:])
            i = len(in_shape)
        elif s == -3:
            out.append(in_shape[i] * in_shape[i + 1])
            i += 2
        elif s == -5:
            prod = 1
            for d in in_shape[i:]:
                prod *= d
            out.append(prod)
            i = len(in_shape)
        elif s == -4:
            d1 = shape[si + 1]
            d2 = shape[si + 2]
            next(it)
            next(it)
            if d1 == -1:
                d1 = in_shape[i] // d2
            if d2 == -1:
                d2 = in_shape[i] // d1
            out.extend([d1, d2])
            i += 1
        elif s == -1:
            out.append(-1)
            i += 1
        elif s == 0:
            out.append(in_shape[i])
            i += 1
        else:
            out.append(s)
            i += 1
    return data.reshape(tuple(out))


def arange_like(data, start=0.0, step=1.0, repeat=1, axis=None):  # noqa: ARG001
    jnp = _jnp()
    if axis is None:
        n = data.size
        return NDArray(jnp.arange(start, start + step * n, step,
                                  dtype=data._data.dtype).reshape(data.shape))
    n = data.shape[axis]
    return NDArray(jnp.arange(start, start + step * n, step, dtype=data._data.dtype))


def shape_array(data):
    jnp = _jnp()
    return NDArray(jnp.asarray(data.shape, dtype=jnp.int64 if False else jnp.int32))


def stop_gradient(data):
    return data.detach()


# ---------------------------------------------------------------------------
# control flow (reference: src/operator/control_flow.cc — foreach/_while_loop/
# _cond as stateful sub-graph ops). TPU-native: in eager mode these run as
# Python loops (tape-friendly); under a jit trace (hybridized block) they
# lower to lax.scan / lax.while_loop / lax.cond so the compiled program
# contains real XLA loop constructs instead of a fully unrolled graph.
# ---------------------------------------------------------------------------

def _is_tracer(x):
    import jax

    from ..ndarray.ndarray import NDArray

    if isinstance(x, NDArray):
        x = x._data
    return isinstance(x, jax.core.Tracer)


def _any_traced(*vals):
    for v in vals:
        if isinstance(v, (list, tuple)):
            if any(_is_tracer(x) for x in v):
                return True
        elif _is_tracer(v):
            return True
    return False


def foreach(body, data, init_states):
    """Run body over axis-0 slices, threading states
    (reference: control_flow.cc foreach ≈ lax.scan; lowers to a real
    lax.scan when traced)."""
    from ..ndarray.ndarray import NDArray

    multi_data = isinstance(data, (list, tuple))
    multi_state = isinstance(init_states, (list, tuple))
    states = list(init_states) if multi_state else [init_states]

    if _any_traced(data, init_states):
        import jax.lax as lax

        xs = ([d._data for d in data] if multi_data else data._data)

        def scan_body(carry, x):
            st = [NDArray(c) for c in carry]
            xi = ([NDArray(v) for v in x] if multi_data else NDArray(x))
            out, new_st = body(xi, st if multi_state else st[0])
            new_st = (list(new_st) if isinstance(new_st, (list, tuple))
                      else [new_st])
            if isinstance(out, (list, tuple)):
                out_vals = tuple(o._data for o in out)
            else:
                out_vals = out._data
            return tuple(s._data for s in new_st), out_vals

        carry0 = tuple(s._data for s in states)
        carry, ys = lax.scan(scan_body, carry0, xs)
        stacked = ([NDArray(y) for y in ys] if isinstance(ys, tuple)
                   else NDArray(ys))
        final = [NDArray(c) for c in carry]
        return stacked, (final if multi_state else final[0])

    outputs = []
    n = data[0].shape[0] if multi_data else data.shape[0]
    for i in range(n):
        x_i = [d[i] for d in data] if multi_data else data[i]
        out, states = body(x_i, states if multi_state else states[0])
        states = (list(states) if isinstance(states, (list, tuple))
                  else [states])
        outputs.append(out)
    from .. import numpy as np_mod

    if outputs and isinstance(outputs[0], (list, tuple)):
        stacked = [np_mod.stack([o[j] for o in outputs])
                   for j in range(len(outputs[0]))]
    else:
        stacked = np_mod.stack(outputs)
    return stacked, (states if multi_state else states[0])


def while_loop(cond, func, loop_vars, max_iterations=None):
    """Loop func while cond holds (reference: control_flow.cc _while_loop).
    Traced: lowers to lax.while_loop; per the reference contract, the
    stacked per-step outputs require `max_iterations` (the output buffer is
    preallocated to that length, tail zeros).

    `cond` and `func` must be PURE (the reference builds them into
    sub-graphs, src/operator/control_flow.cc): with `max_iterations` set,
    `func` may be invoked once as a shape probe even when the loop runs
    zero iterations, so its output shape can match the traced path's
    preallocated buffers."""
    from ..ndarray.ndarray import NDArray

    loop_vars = list(loop_vars)
    if _any_traced(loop_vars):
        import jax
        import jax.lax as lax
        import jax.numpy as jnp

        vals0 = tuple(v._data for v in loop_vars)

        # probe func's output structure with abstract eval
        def _func_flat(*vals):
            out, new_vars = func(*[NDArray(v) for v in vals])
            new_vals = tuple(v._data for v in new_vars)
            if out is None:
                return None, new_vals
            out_vals = (tuple(o._data for o in out)
                        if isinstance(out, (list, tuple)) else out._data)
            return out_vals, new_vals

        out_shape, _ = jax.eval_shape(_func_flat, *vals0)
        has_out = out_shape is not None
        if has_out and max_iterations is None:
            raise ValueError("while_loop with per-step outputs requires "
                             "max_iterations under jit (static buffer size)")

        def cond_fn(carry):
            step, vals, _ = carry
            c = cond(*[NDArray(v) for v in vals])
            c = c._data if isinstance(c, NDArray) else c
            c = jnp.squeeze(c).astype(bool)
            if max_iterations is not None:
                c = jnp.logical_and(c, step < max_iterations)
            return c

        def body_fn(carry):
            step, vals, bufs = carry
            out_vals, new_vals = _func_flat(*vals)
            if has_out:
                if not isinstance(out_vals, tuple):
                    out_vals = (out_vals,)
                bufs = tuple(
                    lax.dynamic_update_index_in_dim(b, o, step, 0)
                    for b, o in zip(bufs, out_vals))
            return step + 1, new_vals, bufs

        if has_out:
            outs = (out_shape if isinstance(out_shape, tuple)
                    else (out_shape,))
            bufs0 = tuple(jnp.zeros((max_iterations,) + o.shape, o.dtype)
                          for o in outs)
        else:
            bufs0 = ()
        steps, vals, bufs = lax.while_loop(
            cond_fn, body_fn, (jnp.asarray(0, jnp.int32), vals0, bufs0))
        new_loop_vars = [NDArray(v) for v in vals]
        if not has_out:
            return None, new_loop_vars
        stacked = [NDArray(b) for b in bufs]
        if not isinstance(out_shape, tuple):
            stacked = stacked[0]
        return stacked, new_loop_vars

    steps = 0
    outputs = []
    while bool(cond(*loop_vars)):
        if max_iterations is not None and steps >= max_iterations:
            break
        out, loop_vars = func(*loop_vars)
        if out is not None:
            outputs.append(out)
        steps += 1
    from .. import numpy as np_mod

    import jax.numpy as jnp

    from ..ndarray.ndarray import NDArray as _ND

    if not outputs:
        if max_iterations is None:
            return None, loop_vars
        # zero iterations but a padded-output contract: probe func (pure by
        # the reference contract) for the per-step output structure so the
        # eager result matches the traced path's zero-filled buffers
        probe_out, _ = func(*loop_vars)
        if probe_out is None:
            return None, loop_vars
        outs = (probe_out if isinstance(probe_out, (list, tuple))
                else [probe_out])
        zeros = [_ND(jnp.zeros((max_iterations,) + tuple(o.shape),
                               o._data.dtype)) for o in outs]
        if isinstance(probe_out, (list, tuple)):
            return zeros, loop_vars
        return zeros[0], loop_vars
    stacked = np_mod.stack(outputs)
    if max_iterations is not None and len(outputs) < max_iterations:
        # pad to max_iterations so eager and traced (lax.while_loop with a
        # preallocated buffer) agree on the output shape — the reference
        # contract: outputs have length max_iterations, tail zeros
        pad_n = max_iterations - len(outputs)
        pad_shape = (pad_n,) + tuple(stacked.shape[1:])
        stacked = np_mod.concatenate(
            [stacked, _ND(jnp.zeros(pad_shape, stacked._data.dtype))])
    return stacked, loop_vars


def cond(pred, then_func, else_func):
    """Conditional (reference: control_flow.cc _cond). Traced: lax.cond."""
    from ..ndarray.ndarray import NDArray

    if _is_tracer(pred):
        import jax.lax as lax
        import jax.numpy as jnp
        import jax.tree_util as jtu

        is_leaf = lambda x: isinstance(x, NDArray)  # noqa: E731
        cell = {}  # captures the output treedef while lax.cond traces

        def leaf_val(o):
            return o._data if isinstance(o, NDArray) else jnp.asarray(o)

        def then_branch(_):
            flat, tree = jtu.tree_flatten(then_func(), is_leaf=is_leaf)
            cell["tree"] = tree
            return tuple(leaf_val(o) for o in flat)

        def else_branch(_):
            flat, _ = jtu.tree_flatten(else_func(), is_leaf=is_leaf)
            return tuple(leaf_val(o) for o in flat)

        p = pred._data if isinstance(pred, NDArray) else pred
        p = jnp.squeeze(p).astype(bool)
        vals = lax.cond(p, then_branch, else_branch, None)
        return jtu.tree_unflatten(cell["tree"], [NDArray(v) for v in vals])

    return then_func() if bool(pred) else else_func()


# ---------------------------------------------------------------------------
# misc module-level utilities
# ---------------------------------------------------------------------------

def boolean_mask(data, index, axis=0):
    """Select rows where index != 0 (reference:
    `src/operator/contrib/boolean_mask.cc` _contrib_boolean_mask — it has a
    backward, so this must too).

    Output shape is data-dependent → the mask is resolved eagerly (like the
    reference's dynamic-shape NaiveRunGraph fallback, SURVEY §7 hard parts),
    then the selection itself is a static gather through the funnel, so
    gradients scatter back into the kept rows. Under jit use
    `np.where`-style masking instead."""
    import numpy as onp

    from ..ndarray.ndarray import NDArray, apply_op_flat

    m = index._data if isinstance(index, NDArray) else index
    m = onp.asarray(m)
    data = data if isinstance(data, NDArray) else NDArray(data)
    if m.shape[0] != data.shape[axis]:
        raise ValueError(
            f"boolean_mask: mask length {m.shape[0]} != data.shape[{axis}] "
            f"= {data.shape[axis]}")
    keep = onp.flatnonzero(m)  # host sync: dynamic shape

    def fn(x):
        import jax.numpy as jnp

        return jnp.take(x, jnp.asarray(keep), axis=axis)

    return apply_op_flat("boolean_mask", fn, (data,))


def clip_global_norm(arrays, max_norm, check_isfinite=True):
    """Rescale arrays in-place so their global L2 norm ≤ max_norm
    (reference: gluon/utils.py clip_global_norm)."""
    jnp = _jnp()
    total = sum(float(jnp.sum(a._data.astype(jnp.float32) ** 2)) for a in arrays)
    total_norm = math.sqrt(total)
    if check_isfinite and not math.isfinite(total_norm):
        raise ValueError("global norm is not finite")
    scale = max_norm / (total_norm + 1e-8)
    if scale < 1.0:
        for a in arrays:
            a._set_data(a._data * scale)
    return total_norm


def set_np(shape=True, array=True, dtype=False):  # noqa: ARG001
    """No-op for parity: this framework is numpy-semantics-native."""
    return True


def reset_np():
    return True


def is_np_array():
    return True


def is_np_shape():
    return True


def waitall():
    from ..ndarray.ndarray import waitall as _w

    _w()


def seed(s):
    from ..random import seed as _s

    _s(s)


def load(fname):
    from ..ndarray import load as _load

    return _load(fname)


def save(fname, data):
    from ..ndarray import save as _save

    return _save(fname, data)
