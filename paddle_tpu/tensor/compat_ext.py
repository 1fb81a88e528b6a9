"""Top-level API parity tail: the reference `paddle.__all__` names that are
compositions/aliases rather than phi ops.

Reference: python/paddle/__init__.py __all__ (430 names). The op-shaped
names come from the YAML-generated binding surface; this module supplies
the remainder — numpy-style stacking/splitting, dtype/value predicates,
in-place functional spellings (`paddle.cos_`), distance/histogram helpers,
scatter-style functional updates, dlpack interop, and small utilities.
Gradient-relevant composites are built from the public op surface (so the
autograd engine sees them); sampling/predicate/integer helpers go straight
to jnp.
"""
from __future__ import annotations

import math as _math

import jax
import jax.numpy as jnp
import numpy as np

from ..core import rng as _rng
from ..core.tensor import Tensor, to_tensor
from ..ops.dispatch import OPS

__all__: list = []   # filled by _public()

inf = float("inf")
newaxis = None


def _public(fn, name=None):
    __all__.append(name or fn.__name__)
    return fn


def _u(x):
    return x._data if isinstance(x, Tensor) else jnp.asarray(x)


def _w(a):
    return Tensor._from_data(a)


def _seq(xs):
    return [x for x in (xs if isinstance(xs, (list, tuple)) else [xs])]


# ---------------------------------------------------------------------------
# numpy-style stacking / splitting (built on public ops: grads flow)
# ---------------------------------------------------------------------------

@_public
def atleast_1d(*inputs):
    outs = [OPS["reshape"](x, [1]) if len(x.shape) == 0 else x
            for x in inputs]
    return outs[0] if len(outs) == 1 else outs


@_public
def atleast_2d(*inputs):
    outs = []
    for x in inputs:
        nd = len(x.shape)
        if nd == 0:
            outs.append(OPS["reshape"](x, [1, 1]))
        elif nd == 1:
            outs.append(OPS["unsqueeze"](x, 0))
        else:
            outs.append(x)
    return outs[0] if len(outs) == 1 else outs


@_public
def atleast_3d(*inputs):
    outs = []
    for x in inputs:
        nd = len(x.shape)
        if nd == 0:
            outs.append(OPS["reshape"](x, [1, 1, 1]))
        elif nd == 1:
            outs.append(OPS["reshape"](x, [1, list(x.shape)[0], 1]))
        elif nd == 2:
            outs.append(OPS["unsqueeze"](x, 2))
        else:
            outs.append(x)
    return outs[0] if len(outs) == 1 else outs


@_public
def hstack(x):
    xs = [atleast_1d(t) for t in _seq(x)]
    axis = 0 if len(xs[0].shape) <= 1 else 1
    return OPS["concat"](xs, axis)


@_public
def vstack(x):
    xs = [atleast_2d(t) for t in _seq(x)]
    return OPS["concat"](xs, 0)


row_stack = _public(vstack, "row_stack")


@_public
def dstack(x):
    xs = [atleast_3d(t) for t in _seq(x)]
    return OPS["concat"](xs, 2)


@_public
def column_stack(x):
    xs = []
    for t in _seq(x):
        xs.append(OPS["unsqueeze"](t, 1) if len(t.shape) == 1 else t)
    return OPS["concat"](xs, 1)


@_public
def tensor_split(x, num_or_indices, axis=0):
    """numpy.array_split semantics (unequal trailing sections allowed)."""
    n = list(x.shape)[axis]
    if isinstance(num_or_indices, int):
        k, m = divmod(n, num_or_indices)
        sizes = [k + 1] * m + [k] * (num_or_indices - m)
        bounds = np.cumsum([0] + sizes)
    else:
        bounds = [0] + [int(i) for i in num_or_indices] + [n]
    outs = []
    for s, e in zip(bounds[:-1], bounds[1:]):
        outs.append(OPS["slice"](x, [axis], [int(s)], [int(e)]))
    return outs


@_public
def hsplit(x, num_or_indices):
    axis = 0 if len(x.shape) == 1 else 1
    return tensor_split(x, num_or_indices, axis=axis)


@_public
def vsplit(x, num_or_indices):
    return tensor_split(x, num_or_indices, axis=0)


@_public
def dsplit(x, num_or_indices):
    return tensor_split(x, num_or_indices, axis=2)


@_public
def unflatten(x, axis, shape):
    old = list(x.shape)
    axis = axis % len(old)
    new = old[:axis] + list(shape) + old[axis + 1:]
    return OPS["reshape"](x, new)


@_public
def view(x, shape_or_dtype):
    if isinstance(shape_or_dtype, (list, tuple)):
        return OPS["view_shape"](x, list(shape_or_dtype))
    return OPS["view_dtype"](x, shape_or_dtype)


@_public
def view_as(x, other):
    return OPS["view_shape"](x, list(other.shape))


@_public
def matrix_transpose(x):
    nd = len(x.shape)
    perm = list(range(nd))
    perm[-1], perm[-2] = perm[-2], perm[-1]
    return OPS["transpose"](x, perm)


@_public
def t(x):
    nd = len(x.shape)
    if nd > 2:
        raise ValueError("paddle.t expects a tensor with ndim <= 2")
    return x if nd < 2 else OPS["transpose"](x, [1, 0])


@_public
def rank(x):
    return to_tensor(len(x.shape), dtype="int32")


@_public
def tolist(x):
    return np.asarray(_u(x)).tolist()


@_public
def broadcast_shape(x_shape, y_shape):
    return list(jnp.broadcast_shapes(tuple(x_shape), tuple(y_shape)))


@_public
def tensordot(x, y, axes=2):
    return _w(jnp.tensordot(_u(x), _u(y), axes=axes))


@_public
def cartesian_prod(x):
    xs = [_u(t).reshape(-1) for t in _seq(x)]
    grids = jnp.meshgrid(*xs, indexing="ij")
    return _w(jnp.stack([g.reshape(-1) for g in grids], axis=-1))


@_public
def combinations(x, r=2, with_replacement=False):
    import itertools

    n = int(np.prod(x.shape)) if len(x.shape) else 1
    it = (itertools.combinations_with_replacement(range(n), r)
          if with_replacement else itertools.combinations(range(n), r))
    idx = np.array(list(it), np.int32).reshape(-1, r)
    flat = _u(x).reshape(-1)
    return _w(flat[idx])


@_public
def vander(x, n=None, increasing=False):
    return _w(jnp.vander(_u(x), N=n, increasing=increasing))


@_public
def block_diag(inputs):
    from jax.scipy.linalg import block_diag as _bd

    return _w(_bd(*[jnp.atleast_2d(_u(t)) for t in _seq(inputs)]))


# ---------------------------------------------------------------------------
# predicates / dtype helpers
# ---------------------------------------------------------------------------

@_public
def is_floating_point(x):
    return jnp.issubdtype(_u(x).dtype, jnp.floating)


@_public
def is_integer(x):
    return jnp.issubdtype(_u(x).dtype, jnp.integer)


@_public
def is_complex(x):
    return jnp.issubdtype(_u(x).dtype, jnp.complexfloating)


@_public
def isneginf(x):
    return _w(jnp.isneginf(_u(x)))


@_public
def isposinf(x):
    return _w(jnp.isposinf(_u(x)))


@_public
def isreal(x):
    return _w(jnp.isreal(_u(x)))


@_public
def isin(x, test_x, assume_unique=False, invert=False):
    return _w(jnp.isin(_u(x), _u(test_x), assume_unique=assume_unique,
                       invert=invert))


@_public
def signbit(x):
    return _w(jnp.signbit(_u(x)))


@_public
def positive(x):
    if _u(x).dtype == jnp.bool_:
        raise TypeError("positive is not supported for bool tensors")
    return x


@_public
def neg(x):
    return OPS["scale"](x, -1.0)


@_public
def sgn(x):
    a = _u(x)
    if jnp.issubdtype(a.dtype, jnp.complexfloating):
        mag = jnp.abs(a)
        return _w(jnp.where(mag == 0, 0, a / jnp.where(mag == 0, 1, mag)))
    return OPS["sign"](x)


@_public
def sinc(x):
    return _w(jnp.sinc(_u(x)))


class iinfo:
    def __init__(self, dtype):
        from ..core.dtype import DType

        info = jnp.iinfo(np.dtype(DType(dtype).name))
        self.min, self.max, self.bits = int(info.min), int(info.max), info.bits
        self.dtype = DType(dtype).name


class finfo:
    def __init__(self, dtype):
        from ..core.dtype import DType

        name = DType(dtype).name
        info = jnp.finfo(name)
        self.min = float(info.min)
        self.max = float(info.max)
        self.eps = float(info.eps)
        self.bits = info.bits
        self.tiny = float(info.tiny)
        self.smallest_normal = float(info.tiny)
        self.resolution = float(info.resolution)
        self.dtype = name


__all__ += ["iinfo", "finfo"]


# ---------------------------------------------------------------------------
# histograms / quantiles / distances / calculus helpers
# ---------------------------------------------------------------------------

@_public
def bucketize(x, sorted_sequence, out_int32=False, right=False):
    side = "right" if right else "left"
    out = jnp.searchsorted(_u(sorted_sequence), _u(x), side=side)
    return _w(out.astype(jnp.int32 if out_int32 else jnp.int64))


@_public
def histogram_bin_edges(x, bins=100, min=0.0, max=0.0):
    rng = None if (min == 0.0 and max == 0.0) else (float(min), float(max))
    return _w(jnp.histogram_bin_edges(_u(x).reshape(-1), bins=bins,
                                      range=rng))


@_public
def histogramdd(x, bins=10, ranges=None, density=False, weights=None):
    h, edges = jnp.histogramdd(_u(x), bins=bins, range=ranges,
                               density=density,
                               weights=None if weights is None
                               else _u(weights))
    return _w(h), [_w(e) for e in edges]


@_public
def nanquantile(x, q, axis=None, keepdim=False, interpolation="linear"):
    out = jnp.nanquantile(_u(x), _u(q) if isinstance(q, Tensor) else q,
                          axis=axis, keepdims=keepdim,
                          method=interpolation)
    return _w(out)


@_public
def cdist(x, y, p=2.0, compute_mode="use_mm_for_euclid_dist_if_necessary"):
    a, b = _u(x), _u(y)
    if p == 2.0 and compute_mode != "donot_use_mm_for_euclid_dist":
        # matmul form: O(n*m) memory instead of the O(n*m*k) difference
        # tensor, and the inner product rides the MXU
        a2 = jnp.sum(a * a, axis=-1)[..., :, None]
        b2 = jnp.sum(b * b, axis=-1)[..., None, :]
        ab = jnp.matmul(a, jnp.swapaxes(b, -1, -2))
        return _w(jnp.sqrt(jnp.maximum(a2 + b2 - 2.0 * ab, 0.0)))
    d = a[..., :, None, :] - b[..., None, :, :]
    if p == 2.0:
        return _w(jnp.sqrt(jnp.sum(d * d, axis=-1) + 0.0))
    if p == float("inf"):
        return _w(jnp.max(jnp.abs(d), axis=-1))
    return _w(jnp.sum(jnp.abs(d) ** p, axis=-1) ** (1.0 / p))


@_public
def pdist(x, p=2.0):
    a = _u(x)
    n = a.shape[0]
    iu = np.triu_indices(n, k=1)
    d = a[iu[0]] - a[iu[1]]
    if p == 2.0:
        return _w(jnp.sqrt(jnp.sum(d * d, axis=-1) + 0.0))
    if p == float("inf"):
        return _w(jnp.max(jnp.abs(d), axis=-1))
    return _w(jnp.sum(jnp.abs(d) ** p, axis=-1) ** (1.0 / p))


@_public
def diff(x, n=1, axis=-1, prepend=None, append=None):
    return _w(jnp.diff(_u(x), n=n, axis=axis,
                       prepend=None if prepend is None else _u(prepend),
                       append=None if append is None else _u(append)))


@_public
def trapezoid(y, x=None, dx=None, axis=-1):
    if x is not None:
        return _w(jnp.trapezoid(_u(y), x=_u(x), axis=axis))
    return _w(jnp.trapezoid(_u(y), dx=1.0 if dx is None else dx, axis=axis))


@_public
def cumulative_trapezoid(y, x=None, dx=None, axis=-1):
    yy = _u(y)
    yy = jnp.moveaxis(yy, axis, -1)
    if x is not None:
        xx = _u(x)
        if xx.ndim > 1:
            xx = jnp.moveaxis(jnp.broadcast_to(xx, _u(y).shape), axis, -1)
        widths = jnp.diff(xx, axis=-1)
    else:
        widths = 1.0 if dx is None else dx
    avg = (yy[..., 1:] + yy[..., :-1]) / 2.0
    out = jnp.cumsum(avg * widths, axis=-1)
    return _w(jnp.moveaxis(out, -1, axis))


@_public
def frexp(x):
    m, e = jnp.frexp(_u(x))
    return _w(m), _w(e.astype(jnp.int32))


@_public
def polar(abs, angle):  # noqa: A002 — paddle's own argument name
    a, th = _u(abs), _u(angle)
    return _w(jax.lax.complex(a * jnp.cos(th), a * jnp.sin(th)))


@_public
def gammainc(x, y):
    from jax.scipy.special import gammainc as _g

    return _w(_g(_u(x), _u(y)))


@_public
def multigammaln(x, p):
    from jax.scipy.special import multigammaln as _mg

    return _w(_mg(_u(x), p))


@_public
def take(x, index, mode="raise"):
    flat = _u(x).reshape(-1)
    idx = _u(index)
    n = flat.shape[0]
    if mode == "wrap":
        idx = jnp.mod(idx, n)
    else:  # 'raise' can't raise inside traced code; clip like paddle's kernel
        idx = jnp.clip(idx, -n, n - 1)
    idx = jnp.where(idx < 0, idx + n, idx)
    return _w(flat[idx])


# ---------------------------------------------------------------------------
# functional scatter/fill updates
# ---------------------------------------------------------------------------

@_public
def scatter_nd(index, updates, shape):
    zeros = OPS["zeros"](list(shape), updates.dtype
                         if hasattr(updates, "dtype") else None)
    return OPS["scatter_nd_add"](zeros, index, updates)


@_public
def slice_scatter(x, value, axes, starts, ends, strides):
    a, v = _u(x), _u(value)
    idx = [slice(None)] * a.ndim
    for ax, s, e, st in zip(axes, starts, ends, strides):
        idx[ax] = slice(int(s), int(e), int(st))
    return _w(a.at[tuple(idx)].set(jnp.broadcast_to(v, a[tuple(idx)].shape)))


@_public
def select_scatter(x, values, axis, index):
    a, v = _u(x), _u(values)
    idx = [slice(None)] * a.ndim
    idx[axis] = int(index)
    return _w(a.at[tuple(idx)].set(v))


@_public
def diagonal_scatter(x, y, offset=0, axis1=0, axis2=1):
    a, v = _u(x), _u(y)
    moved = jnp.moveaxis(a, (axis1, axis2), (-2, -1))
    n, m = moved.shape[-2], moved.shape[-1]
    rows = jnp.arange(max(0, min(n, m - offset) if offset >= 0
                          else min(n + offset, m)))
    if offset >= 0:
        r, c = rows, rows + offset
    else:
        r, c = rows - offset, rows
    out = moved.at[..., r, c].set(v)
    return _w(jnp.moveaxis(out, (-2, -1), (axis1, axis2)))


@_public
def index_fill(x, index, axis, value):
    a = _u(x)
    idx = [slice(None)] * a.ndim
    idx[axis] = _u(index)
    return _w(a.at[tuple(idx)].set(value))


@_public
def masked_scatter(x, mask, value):
    a, m, v = _u(x), _u(mask), _u(value).reshape(-1)
    m = jnp.broadcast_to(m, a.shape)
    # k-th True element takes value[k]: rank the Trues with a cumsum
    order = jnp.cumsum(m.reshape(-1).astype(jnp.int32)) - 1
    picked = v[jnp.clip(order, 0, v.shape[0] - 1)].reshape(a.shape)
    return _w(jnp.where(m, picked.astype(a.dtype), a))


# ---------------------------------------------------------------------------
# sampling
# ---------------------------------------------------------------------------

@_public
def standard_normal(shape, dtype=None, name=None):
    return OPS["gaussian"](list(shape), 0.0, 1.0, dtype)


@_public
def randint_like(x, low=0, high=None, dtype=None):
    if high is None:
        low, high = 0, low
    shape = list(x.shape)
    out = OPS["randint"](low, high, shape)
    if dtype is None:
        dtype = x.dtype  # reference contract: default to x's dtype
    return OPS["cast"](out, dtype)


@_public
def log_normal(mean=1.0, std=2.0, shape=None, name=None):
    shape = [1] if shape is None else list(shape)
    g = OPS["gaussian"](shape, float(mean), float(std), None)
    return OPS["exp"](g)


# ---------------------------------------------------------------------------
# misc utilities
# ---------------------------------------------------------------------------

@_public
def set_printoptions(precision=None, threshold=None, edgeitems=None,
                     sci_mode=None, linewidth=None):
    kw = {}
    if precision is not None:
        kw["precision"] = precision
    if threshold is not None:
        kw["threshold"] = threshold
    if edgeitems is not None:
        kw["edgeitems"] = edgeitems
    if linewidth is not None:
        kw["linewidth"] = linewidth
    if sci_mode is not None:
        kw["suppress"] = not sci_mode
    np.set_printoptions(**kw)


@_public
def disable_signal_handler():
    """Reference: disables paddle's C++ fatal-signal dumpers so other
    frameworks' handlers win. This runtime installs none — no-op."""


@_public
def check_shape(shape):
    """Validate a shape spec (reference: utils/layers_utils.py:484)."""
    if isinstance(shape, Tensor):
        if shape.dtype not in ("int32", "int64"):
            raise TypeError("shape tensor must be int32/int64")
        return
    for ele in shape:
        if isinstance(ele, Tensor):
            continue
        if not isinstance(ele, (int, np.integer)):
            raise TypeError("All elements in `shape` must be integers")
        if ele < 0:
            raise ValueError("All elements in `shape` must be positive")


@_public
def batch(reader, batch_size, drop_last=False):
    """Legacy reader decorator (reference: python/paddle/reader):
    batches an iterable-returning reader into lists of batch_size."""
    def batched():
        buf = []
        for item in reader():
            buf.append(item)
            if len(buf) == batch_size:
                yield buf
                buf = []
        if buf and not drop_last:
            yield buf

    return batched


class LazyGuard:
    """Reference: paddle.LazyGuard delays parameter materialization so huge
    models can be described before memory is committed. Parameters here are
    jax arrays created by initializer calls at Layer construction; this
    guard is a compatibility context — construction inside it behaves
    eagerly (PJRT allocation is lazy enough that describing a model does
    not touch the accelerator until first use)."""

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


__all__.append("LazyGuard")


@_public
def create_parameter(shape, dtype, name=None, attr=None, is_bias=False,
                     default_initializer=None):
    from .. import nn
    from ..core.tensor import Parameter

    if default_initializer is None:
        default_initializer = (nn.initializer.Constant(0.0) if is_bias
                               else nn.initializer.XavierNormal())
    data = default_initializer(list(shape), dtype)
    arr = data._data if isinstance(data, Tensor) else jnp.asarray(data)
    p = Parameter(arr)
    if name:
        p.name = name
    return p


@_public
def from_dlpack(dlpack):
    if hasattr(dlpack, "__dlpack__"):
        try:
            return _w(jnp.from_dlpack(dlpack))
        except Exception:  # backend without dlpack import — host copy
            return to_tensor(np.from_dlpack(dlpack))
    # raw capsule (the reference's to_dlpack output shape): torch is the
    # portable capsule decoder in this image
    import torch.utils.dlpack as _tdl

    return to_tensor(_tdl.from_dlpack(dlpack).numpy())


@_public
def to_dlpack(x):
    a = _u(x)
    try:
        return a.__dlpack__()
    except Exception:
        # PJRT backends without PJRT_Buffer external references: export
        # through host memory
        return np.asarray(a).__dlpack__()


# ---------------------------------------------------------------------------
# in-place functional spellings (`paddle.cos_(x)`) + extra method rebinds
# ---------------------------------------------------------------------------

# base ops with a natural in-place spelling in the reference __all__
_INPLACE_TAIL = [
    "cos", "sin", "tan", "sinh", "acos", "atan", "expm1", "erf", "log",
    "log2", "log10", "log1p", "trunc", "frac", "digamma", "lgamma",
    "gammaln", "cumsum", "cumprod", "logit", "neg", "i0", "polygamma",
    "nan_to_num", "square", "gcd", "lcm", "hypot", "copysign", "ldexp",
    "renorm", "addmm", "where", "equal", "less_than", "less_equal",
    "greater_than", "greater_equal", "not_equal", "logical_and",
    "logical_or", "logical_not", "logical_xor", "bitwise_and", "bitwise_or",
    "bitwise_xor", "bitwise_not", "floor_divide", "tril", "triu",
    "bitwise_left_shift", "bitwise_right_shift", "gammainc", "gammaincc",
    "multigammaln", "sinc", "scatter", "transpose", "t", "masked_scatter",
    "index_fill",
]

_LOCAL_BASES = {"neg": neg, "sinc": sinc, "multigammaln": multigammaln,
                "gammainc": gammainc, "t": t, "masked_scatter": masked_scatter,
                "index_fill": index_fill}


def _base_fn(base):
    if base in OPS:
        return OPS[base]
    return _LOCAL_BASES.get(base)


def _install_inplace_tail():
    for base in _INPLACE_TAIL:
        fn = _base_fn(base)
        if fn is None:
            continue
        iname = base + "_"

        def make(f):
            def method(self, *args, **kwargs):
                return self._rebind(f(self, *args, **kwargs))

            return method

        if not hasattr(Tensor, iname):
            setattr(Tensor, iname, make(fn))

        def make_mod(nm):
            def mod_fn(x, *args, **kwargs):
                return getattr(x, nm)(*args, **kwargs)

            mod_fn.__name__ = nm
            return mod_fn

        globals().setdefault(iname, make_mod(iname))
        if iname not in __all__:
            __all__.append(iname)


_install_inplace_tail()

# where_'s paddle signature leads with the condition, not the output tensor
def where_(condition, x, y):  # noqa: E302 — grouped with the installer
    return x._rebind(OPS["where"](condition, x, y))


globals()["where_"] = where_
if "where_" in __all__:
    __all__.remove("where_")
__all__.append("where_")


def _sample_inplace():
    def bernoulli_(self, p=0.5):
        key = _rng.next_key()
        return self._rebind(_w(jax.random.bernoulli(
            key, p, tuple(self.shape)).astype(_u(self).dtype)))

    def cauchy_(self, loc=0, scale=1):
        key = _rng.next_key()
        u = jax.random.uniform(key, tuple(self.shape)) - 0.5
        return self._rebind(_w((loc + scale * jnp.tan(np.pi * u))
                               .astype(_u(self).dtype)))

    def geometric_(self, probs):
        key = _rng.next_key()
        u = jax.random.uniform(key, tuple(self.shape), minval=1e-12,
                               maxval=1.0)
        out = jnp.floor(jnp.log(u) / jnp.log1p(-jnp.asarray(probs))) + 1.0
        return self._rebind(_w(out.astype(_u(self).dtype)))

    def log_normal_(self, mean=1.0, std=2.0):
        key = _rng.next_key()
        g = mean + std * jax.random.normal(key, tuple(self.shape))
        return self._rebind(_w(jnp.exp(g).astype(_u(self).dtype)))

    for name, fn in [("bernoulli_", bernoulli_), ("cauchy_", cauchy_),
                     ("geometric_", geometric_), ("log_normal_", log_normal_)]:
        if not hasattr(Tensor, name):
            setattr(Tensor, name, fn)

        def make_mod(nm):
            def mod_fn(x, *args, **kwargs):
                return getattr(x, nm)(*args, **kwargs)

            mod_fn.__name__ = nm
            return mod_fn

        globals().setdefault(name, make_mod(name))
        if name not in __all__:
            __all__.append(name)


_sample_inplace()

# simple function aliases of existing surface ------------------------------

def _alias(name, target):
    globals()[name] = target
    __all__.append(name)


_alias("less", OPS.get("less_than"))
_alias("mod", OPS.get("remainder"))
_alias("floor_mod", OPS.get("remainder"))
_alias("bitwise_invert", OPS.get("bitwise_not"))
if OPS.get("bitwise_not") is not None:
    _alias("bitwise_invert_",
           lambda x, *a, **k: x._rebind(OPS["bitwise_not"](x, *a, **k)))
_alias("abs_", lambda x: x.abs_())
_alias("normal_", lambda x, mean=0.0, std=1.0: x.normal_(mean, std))

# module-level functional spellings of method-only in-place variants
# (Tensor.<name>_ was installed by tensor/__init__.py's rebind machinery)
_METHOD_INPLACE = ["unsqueeze_", "squeeze_", "remainder_", "pow_", "divide_",
                   "cast_", "tanh_", "flatten_", "multiply_", "reshape_",
                   "masked_fill_", "add_", "subtract_", "scale_", "clip_",
                   "exp_", "sqrt_", "rsqrt_", "reciprocal_", "floor_",
                   "ceil_", "round_", "sigmoid_", "relu_", "erfinv_",
                   "lerp_", "index_add_", "zero_", "fill_", "uniform_",
                   "exponential_"]
for _mname in _METHOD_INPLACE:
    if hasattr(Tensor, _mname) and _mname not in globals():
        def _make_delegate(nm):
            def fn(x, *args, **kwargs):
                return getattr(x, nm)(*args, **kwargs)

            fn.__name__ = nm
            return fn

        _alias(_mname, _make_delegate(_mname))
del _mname
_alias("mod_", globals().get("remainder_"))
_alias("floor_mod_", globals().get("remainder_"))
_alias("less_", globals().get("less_than_"))

__all__ += ["inf", "newaxis"]


class _OpaqueDType:
    """Sentinels for the reference's non-numeric dtypes (pstring: string
    tensors, served by the strings op family; raw: untyped buffers)."""

    __slots__ = ("name",)

    def __init__(self, name):
        self.name = name

    def __repr__(self):
        return f"paddle.{self.name}"

    def __eq__(self, other):
        return (isinstance(other, _OpaqueDType) and other.name == self.name) \
            or other == self.name

    def __hash__(self):
        return hash(self.name)


pstring = _OpaqueDType("pstring")
raw = _OpaqueDType("raw")
__all__ += ["pstring", "raw"]


# ---------------------------------------------------------------------------
# linalg lowrank / factor helpers (reference tensor_method_func names)
# ---------------------------------------------------------------------------

@_public
def cholesky_inverse(x, upper=False):
    """(A)^-1 from its Cholesky factor (reference: linalg
    cholesky_inverse): A = L L^T (or U^T U). Batched inputs transpose the
    last two axes only."""
    a = _u(x)
    eye = jnp.broadcast_to(jnp.eye(a.shape[-1], dtype=a.dtype), a.shape)
    if upper:
        a = jnp.swapaxes(a, -1, -2)
    inv_l = jax.scipy.linalg.solve_triangular(a, eye, lower=True)
    return _w(jnp.swapaxes(inv_l, -1, -2) @ inv_l)


def _lowrank_svd(a, q, niter):
    """Shared Halko sketch (+ subspace iteration): returns (U, S, V) with
    V column-major (a ≈ U diag(S) V^T). Used by svd_lowrank here and
    sparse.pca_lowrank."""
    m, n = a.shape[-2], a.shape[-1]
    q = min(q, m, n)
    key = jax.random.PRNGKey(0)
    omega = jax.random.normal(key, (*a.shape[:-2], n, q), a.dtype)
    y = a @ omega
    for _ in range(niter):
        y = a @ (jnp.swapaxes(a, -1, -2) @ y)
    qmat, _ = jnp.linalg.qr(y)
    b = jnp.swapaxes(qmat, -1, -2) @ a
    u_b, s, vt = jnp.linalg.svd(b, full_matrices=False)
    return qmat @ u_b, s, jnp.swapaxes(vt, -1, -2)


@_public
def svd_lowrank(x, q=6, niter=2, M=None, name=None):
    """Randomized low-rank SVD (reference: linalg svd_lowrank)."""
    a = _u(x)
    if M is not None:
        a = a - _u(M)
    u, s, v = _lowrank_svd(a, q, niter)
    return _w(u), _w(s), _w(v)


@_public
def pca_lowrank(x, q=None, center=True, niter=2, name=None):
    """Dense PCA sketch (reference: linalg pca_lowrank); the sparse entry
    point lives in paddle.sparse."""
    from ..sparse import pca_lowrank as _sp

    return _sp(x, q=q, center=center, niter=niter)


@_public
def ormqr(x, tau, y, left=True, transpose=False):
    """Multiply by Q from a QR factorization's householder form
    (reference: linalg ormqr). Q is the FULL m x m orthogonal factor, so
    the householder vectors are zero-padded to square before the
    product."""
    a, tv = _u(x), _u(tau)
    m, n = a.shape[-2], a.shape[-1]
    if n < m:
        pad_a = [(0, 0)] * (a.ndim - 1) + [(0, m - n)]
        a = jnp.pad(a, pad_a)
        pad_t = [(0, 0)] * (tv.ndim - 1) + [(0, m - tv.shape[-1])]
        tv = jnp.pad(tv, pad_t)
    q = jax.lax.linalg.householder_product(a, tv)
    mat = jnp.swapaxes(q, -1, -2) if transpose else q
    other = _u(y)
    return _w(mat @ other if left else other @ mat)


@_public
def create_tensor(dtype, name=None, persistable=False):
    """Reference: paddle.tensor.creation.create_tensor — an empty
    placeholder tensor of the given dtype."""
    return _w(jnp.zeros((0,), np.dtype(str(dtype))
                        if str(dtype) != "bfloat16" else jnp.bfloat16))


# in-place variants of scatter-style ops + trig tail + set_
def _more_inplace():
    extra = ["acosh", "asin", "asinh", "atanh", "cosh", "put_along_axis",
             "index_put"]
    for base in extra:
        fn = OPS.get(base)
        if fn is None:
            continue
        iname = base + "_"
        if not hasattr(Tensor, iname):
            def make(f):
                def method(self, *args, **kwargs):
                    return self._rebind(f(self, *args, **kwargs))

                return method

            setattr(Tensor, iname, make(fn))

        def make_mod(nm):
            def mod_fn(x, *args, **kwargs):
                return getattr(x, nm)(*args, **kwargs)

            mod_fn.__name__ = nm
            return mod_fn

        globals().setdefault(iname, make_mod(iname))
        if iname not in __all__:
            __all__.append(iname)

    def set_(self, source=None, shape=None):
        """Rebind this tensor's buffer to `source` (reference Tensor.set_)."""
        if source is None:
            return self._rebind(_w(jnp.zeros((0,), _u(self).dtype)))
        arr = _u(source)
        if shape is not None:
            arr = arr.reshape(shape)
        return self._rebind(_w(arr))

    if not hasattr(Tensor, "set_"):
        Tensor.set_ = set_


_more_inplace()


# patch the compat surface onto Tensor as methods (the reference's
# tensor_method_func list includes these names)
_METHOD_NAMES = [
    "atleast_1d", "atleast_2d", "atleast_3d", "bitwise_invert",
    "bitwise_invert_", "block_diag", "broadcast_shape", "bucketize",
    "cdist", "cholesky_inverse", "create_parameter", "create_tensor",
    "cumulative_trapezoid", "diagonal_scatter", "diff", "dsplit",
    "frexp", "gammainc", "histogram_bin_edges", "histogramdd", "hsplit",
    "index_fill", "is_complex", "is_floating_point", "is_integer",
    "isin", "isneginf", "isposinf", "isreal", "less", "less_",
    "masked_scatter", "mod_", "floor_mod_", "multigammaln",
    "nanquantile", "neg", "ormqr", "pca_lowrank", "polar", "scatter_nd",
    "select_scatter", "sgn", "signbit", "sinc", "slice_scatter",
    "svd_lowrank", "take", "tensor_split", "tensordot", "trapezoid",
    "unflatten", "vander", "view", "view_as", "vsplit",
]


def _patch_methods():
    from ..ops.dispatch import OPS as _ops

    for name in _METHOD_NAMES:
        if hasattr(Tensor, name):
            continue
        fn = globals().get(name) or _ops.get(name)
        if fn is not None:
            setattr(Tensor, name, fn)
    # module-level helpers that are tensor methods in the reference
    if not hasattr(Tensor, "multi_dot"):
        Tensor.multi_dot = lambda self, *rest: _ops["multi_dot"](
            [self, *rest])
    if not hasattr(Tensor, "is_tensor"):
        Tensor.is_tensor = lambda self: True
    if not hasattr(Tensor, "istft"):
        def istft(self, *args, **kwargs):
            from .. import signal

            return signal.istft(self, *args, **kwargs)

        Tensor.istft = istft


_patch_methods()
