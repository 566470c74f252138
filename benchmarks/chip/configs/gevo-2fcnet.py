"""GEVO-ML's 2fcNet search: the initial weights the benchmark makes, and the
plain reference of a candidate's fitness error.

A candidate is an HLO-lite program for one SGD step of a 784-128-10 network
(GEVO-ML, arXiv:2310.10211, section 5).  Its fitness error is what the
paper's protocol gives: start from the initial weights, run the step over
consecutive batches of the training set (cycling), feed its outputs back as
the weights, then classify the test set with the plain forward pass
``relu(x w1 + b1) w2 + b2`` and count the misses.

The reference does that with its own interpreter of the program's
operations, in NumPy on the host, one operation at a time.  It reads the
candidate only through the attributes of its operation list (``inputs``,
``ops``, ``outputs``) and imports nothing of the program.  Precisions:

* ``"f32_mm_bf16"``: the precision the configuration states: float32
  values, the operands of every matrix product rounded to bfloat16 and the
  products summed in float32 (what one bfloat16 pass of a TPU computes);
* ``"f64_mm_bf16"``: the same operand rounding, every value carried in
  float64 (the rounding probe: where it and ``"f32_mm_bf16"`` disagree,
  rounding alone moves the candidate's result);
* ``"bf16"``: every floating value rounded to bfloat16 after each
  operation (the control);
* ``"f32"`` and ``"f64"``: exact products in that type.
"""

from __future__ import annotations

import numpy as np

WEIGHTS = ("w1", "b1", "w2", "b2")


def make_weights(doc: dict, seed: int) -> dict[str, np.ndarray]:
    """He-normal initial weights from ``seed`` (biases zero), float32."""
    w = doc["workload"]
    d_in, hid, cls = w["in_dim"], w["hidden"], w["classes"]
    rng = np.random.default_rng([int(seed), 7])
    return {
        "w1": (rng.standard_normal((d_in, hid))
               * np.sqrt(2.0 / d_in)).astype(np.float32),
        "b1": np.zeros(hid, np.float32),
        "w2": (rng.standard_normal((hid, cls))
               * np.sqrt(2.0 / hid)).astype(np.float32),
        "b2": np.zeros(cls, np.float32),
    }


def _smooth(rng, shape, passes: int) -> np.ndarray:
    x = rng.standard_normal(shape).astype(np.float32)
    for _ in range(passes):
        x = (x + np.roll(x, 1, 0) + np.roll(x, -1, 0)
             + np.roll(x, 1, 1) + np.roll(x, -1, 1)) / 5.0
    return x


def make_data(doc: dict, seed: int) -> dict[str, np.ndarray]:
    """A stand-in for MNIST from ``seed``, at MNIST's shapes: ten smooth
    class prototypes of 28 x 28, each example its class's prototype rolled
    by up to ``jitter`` pixels, scaled by ``1 + 0.2 normal`` and plus
    ``noise`` times normal pixel noise (the recipe of the repository's
    synthetic MNIST, drawn here from the seed).  Float32 images flattened
    to rows, int32 labels; the first ``n_train`` rows train, the rest test."""
    w, d = doc["workload"], doc["data"]
    side, cls = int(np.sqrt(w["in_dim"])), w["classes"]
    n_train, n = w["n_train"], w["n_train"] + w["n_test"]
    rng = np.random.default_rng([int(seed), 11])
    protos = np.stack([_smooth(rng, (side, side), d["smooth_passes"])
                       for _ in range(cls)])
    protos /= np.abs(protos).max(axis=(1, 2), keepdims=True) + 1e-6
    labels = rng.integers(0, cls, n).astype(np.int32)
    shifts = rng.integers(-d["jitter"], d["jitter"] + 1, (n, 2))
    scales = 1.0 + 0.2 * rng.standard_normal(n)
    noise = d["noise"] * rng.standard_normal((n, side, side))
    x = np.empty((n, side, side), np.float32)
    for i in range(n):
        p = np.roll(protos[labels[i]], tuple(shifts[i]), (0, 1))
        x[i] = scales[i] * p + noise[i]
    x = x.reshape(n, side * side)
    return {"train_x": x[:n_train], "train_y": labels[:n_train],
            "test_x": x[n_train:], "test_y": labels[n_train:]}


def _bf16(x: np.ndarray) -> np.ndarray:
    """Round float32 to the nearest bfloat16 (ties to even), kept in a
    float32 container; NaN and infinity pass through."""
    x = np.ascontiguousarray(x, np.float32)
    u = x.view(np.uint32)
    r = ((u + np.uint32(0x7FFF) + ((u >> np.uint32(16)) & np.uint32(1)))
         & np.uint32(0xFFFF0000)).view(np.float32)
    return np.where(np.isfinite(x), r, x).reshape(x.shape)


_NP = {"f32": np.float32, "bf16": np.float32, "i32": np.int32,
       "bool": np.bool_}
_BIN = {"add": np.add, "subtract": np.subtract, "multiply": np.multiply,
        "divide": np.divide, "maximum": np.maximum, "minimum": np.minimum,
        "power": np.power}
_UN = {"exponential": np.exp, "log": np.log, "negate": np.negative,
       "tanh": np.tanh, "rsqrt": lambda a: 1.0 / np.sqrt(a),
       "abs": np.abs, "sign": np.sign}
_CMP = {"EQ": np.equal, "NE": np.not_equal, "LT": np.less,
        "LE": np.less_equal, "GT": np.greater, "GE": np.greater_equal}


def _dot(a, b, dims):
    (lc, rc), (lb, rb) = dims
    letters = iter("abcdefghijklmnopqrstuvwxyz")
    la = [next(letters) for _ in range(a.ndim)]
    lb_ = [next(letters) for _ in range(b.ndim)]
    for i, j in zip(lc, rc):
        lb_[j] = la[i]
    for i, j in zip(lb, rb):
        lb_[j] = la[i]
    out = ([la[i] for i in lb]
           + [la[i] for i in range(a.ndim) if i not in lc and i not in lb]
           + [lb_[j] for j in range(b.ndim) if j not in rc and j not in rb])
    spec = f"{''.join(la)},{''.join(lb_)}->{''.join(out)}"
    return np.einsum(spec, a, b)


def _pad(x, low, high, value):
    widths = [(max(l, 0), max(h, 0)) for l, h in zip(low, high)]
    y = np.pad(x, widths, constant_values=np.asarray(value, x.dtype))
    sl = tuple(slice(-l if l < 0 else 0, y.shape[i] + h if h < 0 else None)
               for i, (l, h) in enumerate(zip(low, high)))
    return y[sl]


def _bf16_operand(x: np.ndarray) -> np.ndarray:
    """A floating operand rounded to bfloat16, kept in its own type."""
    if x.dtype.kind != "f":
        return x
    return _bf16(x.astype(np.float32)).astype(x.dtype)


def _op(op, xs, mm_bf16: bool = False):
    a, oc = op.attrs, op.opcode
    if oc in _BIN:
        return _BIN[oc](xs[0], xs[1])
    if oc in _UN:
        return _UN[oc](xs[0])
    if oc == "constant":
        return np.asarray(a["value"], _NP[a.get("dtype", "f32")])
    if oc == "dot":
        if mm_bf16:
            xs = [_bf16_operand(x) for x in xs]
        return _dot(xs[0], xs[1], a.get("dims", (((1,), (0,)), ((), ()))))
    if oc == "reshape":
        return np.reshape(xs[0], tuple(a["new_shape"]))
    if oc == "broadcast_in_dim":
        shape, bd = tuple(a["shape"]), tuple(a["broadcast_dimensions"])
        expanded = [1] * len(shape)
        for i, d in enumerate(bd):
            expanded[d] = xs[0].shape[i]
        return np.broadcast_to(np.reshape(xs[0], expanded), shape).copy()
    if oc == "transpose":
        return np.transpose(xs[0], tuple(a["permutation"]))
    if oc == "reduce_sum":
        return np.sum(xs[0], axis=tuple(a["dims"]), dtype=xs[0].dtype)
    if oc == "reduce_max":
        return np.max(xs[0], axis=tuple(a["dims"]))
    if oc == "pad":
        return _pad(xs[0], a["low"], a["high"], a.get("value", 0.0))
    if oc == "slice":
        strides = a.get("strides", (1,) * xs[0].ndim)
        return xs[0][tuple(slice(s, l, st) for s, l, st
                           in zip(a["start"], a["limit"], strides))]
    if oc == "select":
        return np.where(xs[0], xs[1], xs[2])
    if oc == "compare":
        return _CMP[a["direction"]](xs[0], xs[1])
    if oc == "convert":
        return xs[0].astype(_NP[a["new_dtype"]])
    raise NotImplementedError(f"the reference has no {oc!r}")


PRECISIONS = ("f32", "f64", "bf16", "f32_mm_bf16", "f64_mm_bf16")


def _dtypes(precision: str) -> dict:
    if precision.startswith("f64"):
        return dict(_NP, f32=np.float64, bf16=np.float64)
    return _NP


def run_program(program, inputs: dict, precision: str = "f32") -> list:
    """Execute ``program`` on named NumPy inputs, one operation at a time,
    in ``precision`` (one of :data:`PRECISIONS`)."""
    if precision not in PRECISIONS:
        raise ValueError(f"unknown precision {precision!r}")
    types = _dtypes(precision)
    bf16 = precision == "bf16"
    mm_bf16 = precision.endswith("_mm_bf16")
    env = {}
    for name, vid, ttype in program.inputs:
        x = np.asarray(inputs[name], types[ttype.dtype])
        env[vid] = _bf16(x) if bf16 or ttype.dtype == "bf16" else x
    for op in program.ops:
        xs = [env[o] for o in op.operands]
        if op.opcode == "constant":
            y = np.asarray(op.attrs["value"], types[op.attrs.get("dtype",
                                                                 "f32")])
        elif op.opcode == "convert":
            y = xs[0].astype(types[op.attrs["new_dtype"]])
        else:
            y = np.asarray(_op(op, xs, mm_bf16))
        y = y.astype(types[op.type.dtype], copy=False)
        if y.dtype == np.float32 and (bf16 or op.type.dtype == "bf16"):
            y = _bf16(y)
        env[op.result] = y
    return [env[o] for o in program.outputs]


def train_error(doc: dict, program, init: dict, data: dict,
                precision: str = "f32") -> float | None:
    """The candidate's fitness error by the paper's protocol, or ``None``
    where training breaks (a weight changes shape or ends non-finite)."""
    w = doc["workload"]
    batch, steps, classes = w["batch"], w["steps"], w["classes"]
    xtr, ytr = data["train_x"], data["train_y"]
    n = (len(xtr) // batch) * batch
    eye = np.eye(classes, dtype=np.float32)
    ftype = np.float64 if precision.startswith("f64") else np.float32
    weights = {k: v.astype(ftype) for k, v in init.items()}
    with np.errstate(all="ignore"):
        for step in range(steps):
            j = (step * batch) % n
            inputs = dict(weights, x=xtr[j:j + batch],
                          y_onehot=eye[ytr[j:j + batch]])
            outs = run_program(program, inputs, precision)
            if len(outs) != len(WEIGHTS):
                return None
            for k, o in zip(WEIGHTS, outs):
                if o.shape != init[k].shape:
                    return None
                weights[k] = o.astype(ftype)
        if not all(np.all(np.isfinite(v)) for v in weights.values()):
            return None
        r = _bf16 if precision == "bf16" else (lambda a: a)
        m = _bf16_operand if precision.endswith("_mm_bf16") else r
        x, y = r(data["test_x"].astype(ftype)), data["test_y"]
        h = np.maximum(r(r(m(x) @ m(weights["w1"])) + r(weights["b1"])),
                       0.0)
        logits = r(r(m(h) @ m(weights["w2"])) + r(weights["b2"]))
    return float(1.0 - np.mean(np.argmax(logits, -1) == y))
