"""Plain float32 reference of the benchmark's conv nets, and the comparison.

A configuration's reference (``configs/<name>.py``) describes its net as a
list of layers, built from the published sizes in ``configs/<name>.json``.
This module runs such a list with nothing but ``jax.lax`` in NCHW,
draws its weights from the seed, and compares served outputs with it.
It imports nothing of the program under test.

A layer is a dict: ``name``, ``op``, ``inputs`` (names of earlier layers,
``"data"`` for the image) and the op's sizes:

* ``conv``: ``k``, ``m``, ``stride``, ``pad``, optional ``gain`` (1.0) --
  cross-correlation plus bias;
* ``relu``;
* ``maxpool``: ``k``, ``stride``, ``pad`` (padding never wins the max);
* ``lrn``: ``size``, ``alpha``, ``beta``, ``bias`` -- across channels,
  ``x / (bias + alpha/size * sum x^2) ** beta``;
* ``concat``: along channels;
* ``add``: the elementwise sum of two or more inputs of one shape (a
  residual join);
* ``gap``: global average pool to ``(C, 1, 1)``;
* ``fc``: ``out``, ``relu`` -- flattens each image in (C, H, W) order.

The last layer's output is the logits; the served net ends in a softmax
over them.

Weights follow the seeded He initialisation that the served stack
derives from its ``params_seed``: one ``numpy.random.default_rng(seed)``
drawn layer by layer in list order; a conv draws its ``(M, C, K, K)``
weights from N(0, 2/(C K K)) and then its bias from N(0, 0.01^2); an fc
draws its ``(in, out)`` weights from N(0, 2/in) and has a zero bias.

A conv's ``gain`` scales the std of its weights' draw and nothing else:
``rng.normal(0, gain * sqrt(2/(C K K)))``, from the same stream in the
same list order, with the bias drawn as before.  It is a folded
inference BatchNorm whose scale gamma/sigma is ``gain`` in every
channel, as on a residual branch's last conv: without it each identity
join of the He draw about doubles the activations' second moment, and a
deep residual net's softmax keeps a single class.  A served net drawn
with ``gain`` has to draw the same bits in ``Net.init_params``: the
same stream, in this list's order.  A conv without ``gain`` draws with
the std ``sqrt(2/(C K K))`` itself, bit for bit.
"""
from __future__ import annotations

from typing import Callable, Dict, List, Sequence, Tuple

import numpy as np

Layer = Dict
Params = Dict[str, Dict[str, np.ndarray]]


def shapes(layers: Sequence[Layer], input_chw: Tuple[int, int, int]
           ) -> Dict[str, Tuple[int, ...]]:
    """Per-image output shape of every layer, ``"data"`` included."""
    out: Dict[str, Tuple[int, ...]] = {"data": tuple(input_chw)}
    for ly in layers:
        ins = [out[i] for i in ly["inputs"]]
        c, h, w = ins[0]
        op = ly["op"]
        if op == "conv":
            oh = (h + 2 * ly["pad"] - ly["k"]) // ly["stride"] + 1
            ow = (w + 2 * ly["pad"] - ly["k"]) // ly["stride"] + 1
            out[ly["name"]] = (ly["m"], oh, ow)
        elif op == "maxpool":
            oh = (h + 2 * ly["pad"] - ly["k"]) // ly["stride"] + 1
            ow = (w + 2 * ly["pad"] - ly["k"]) // ly["stride"] + 1
            out[ly["name"]] = (c, oh, ow)
        elif op in ("relu", "lrn"):
            out[ly["name"]] = (c, h, w)
        elif op == "concat":
            out[ly["name"]] = (sum(s[0] for s in ins), h, w)
        elif op == "add":
            if len(ins) < 2 or any(s != ins[0] for s in ins):
                raise ValueError(f"add {ly['name']} needs two or more inputs "
                                 f"of one shape, got {ins}")
            out[ly["name"]] = ins[0]
        elif op == "gap":
            out[ly["name"]] = (c, 1, 1)
        elif op == "fc":
            out[ly["name"]] = (ly["out"], 1, 1)
        else:
            raise ValueError(f"unknown op {op!r} in layer {ly['name']}")
    return out


def init_params(layers: Sequence[Layer], input_chw: Tuple[int, int, int],
                seed: int) -> Params:
    """Weights of every conv and fc layer, drawn from ``seed``."""
    shp = shapes(layers, input_chw)
    rng = np.random.default_rng(seed)
    params: Params = {}
    for ly in layers:
        if ly["op"] == "conv":
            c = shp[ly["inputs"][0]][0]
            std = ly.get("gain", 1.0) * float(
                np.sqrt(2.0 / (c * ly["k"] * ly["k"])))
            params[ly["name"]] = {
                "w": rng.normal(0, std, size=(ly["m"], c, ly["k"], ly["k"]))
                        .astype(np.float32),
                "b": rng.normal(0, 0.01, size=(ly["m"],)).astype(np.float32),
            }
        elif ly["op"] == "fc":
            n_in = int(np.prod(shp[ly["inputs"][0]]))
            params[ly["name"]] = {
                "w": rng.normal(0, float(np.sqrt(2.0 / n_in)),
                                size=(n_in, ly["out"])).astype(np.float32),
                "b": np.zeros((ly["out"],), np.float32),
            }
    return params


# ----------------------------------------------------------------------
# precision of the forward pass
# ----------------------------------------------------------------------
def _keep(x, axis=None):
    return x


def _bf16(x, axis=None):
    import jax.numpy as jnp
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def _int8(x, axis=None):
    """Symmetric int8 fake quantisation: one scale per tensor, or per
    slice along ``axis`` (a weight's output channel)."""
    import jax.numpy as jnp
    if axis is None:
        amax = jnp.max(jnp.abs(x))
    else:
        red = tuple(i for i in range(x.ndim) if i != axis)
        amax = jnp.max(jnp.abs(x), axis=red, keepdims=True)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    return jnp.clip(jnp.round(x / scale), -127, 127) * scale


#: name -> (operand rounding, weight rounding, rounding of every stored
#: activation).  ``float32`` is the reference.  The configurations state
#: bfloat16 compute, and ``int8`` is the control a step below it; the
#: two bfloat16 entries are readings beside it, of what the MXU's own
#: operand rounding and bfloat16 storage cost.
PRECISIONS: Dict[str, Tuple[Callable, Callable, Callable]] = {
    "float32": (_keep, _keep, _keep),
    # conv and fc operands rounded to bfloat16, float32 accumulation and
    # storage: a float32 conv at a TPU's default matmul precision
    "bf16_operands": (_bf16, _bf16, _keep),
    # every tensor stored in bfloat16; products accumulate in float32,
    # as on the MXU
    "bfloat16": (_bf16, _bf16, _bf16),
    # int8 operands of every conv and fc (activations per tensor,
    # weights per output channel), float32 accumulation and storage
    "int8": (_int8, _int8, _keep),
}


def forward(layers: Sequence[Layer], params, x, precision: str = "float32"):
    """Logits of a batch ``x`` (N, C, H, W) under the layer list.

    Run it under ``jax.default_matmul_precision("highest")``: on a TPU a
    float32 conv is otherwise computed from bfloat16 operands.
    """
    import jax.numpy as jnp
    from jax import lax

    operand, weight, store = PRECISIONS[precision]
    vals = {"data": store(x)}
    for ly in layers:
        ins = [vals[i] for i in ly["inputs"]]
        v = ins[0]
        op = ly["op"]
        if op == "conv":
            p = params[ly["name"]]
            y = lax.conv_general_dilated(
                operand(v), weight(p["w"], 0), (ly["stride"],) * 2,
                [(ly["pad"], ly["pad"])] * 2,
                dimension_numbers=("NCHW", "OIHW", "NCHW"))
            y = y + p["b"][None, :, None, None]
        elif op == "relu":
            y = jnp.maximum(v, 0.0)
        elif op == "maxpool":
            k, s, pd = ly["k"], ly["stride"], ly["pad"]
            y = lax.reduce_window(v, -jnp.inf, lax.max, (1, 1, k, k),
                                  (1, 1, s, s),
                                  ((0, 0), (0, 0), (pd, pd), (pd, pd)))
        elif op == "lrn":
            half = ly["size"] // 2
            sq = lax.reduce_window(v * v, 0.0, lax.add,
                                   (1, ly["size"], 1, 1), (1, 1, 1, 1),
                                   ((0, 0), (half, half), (0, 0), (0, 0)))
            y = v / (ly["bias"] + ly["alpha"] / ly["size"] * sq) ** ly["beta"]
        elif op == "concat":
            y = jnp.concatenate(ins, axis=1)
        elif op == "add":
            y = sum(ins[1:], ins[0])
        elif op == "gap":
            y = jnp.mean(v, axis=(2, 3), keepdims=True)
        elif op == "fc":
            p = params[ly["name"]]
            flat = v.reshape(v.shape[0], -1)
            y = jnp.dot(operand(flat), weight(p["w"], 1)) + p["b"]
            if ly["relu"]:
                y = jnp.maximum(y, 0.0)
            y = y[:, :, None, None]
        else:
            raise ValueError(f"unknown op {op!r} in layer {ly['name']}")
        vals[ly["name"]] = store(y)
    return vals[layers[-1]["name"]].reshape(x.shape[0], -1)


def logits(layers: Sequence[Layer], params: Params, images: np.ndarray,
           precision: str = "float32", block: int = 8) -> np.ndarray:
    """Reference logits of ``images`` (N, C, H, W) on the default device,
    ``block`` images at a time, at the highest matmul precision."""
    import jax
    import jax.numpy as jnp

    fn = jax.jit(lambda p, x: forward(layers, p, x, precision))
    dev = jax.tree.map(jnp.asarray, params)
    out = []
    with jax.default_matmul_precision("highest"):
        for i in range(0, len(images), block):
            x = np.asarray(images[i:i + block], np.float32)
            n = len(x)
            if n < block:  # one compiled shape: pad the last block
                x = np.concatenate([x, np.zeros((block - n, *x.shape[1:]),
                                                np.float32)])
            out.append(np.asarray(fn(dev, x))[:n])
    return np.concatenate(out).astype(np.float64)


# ----------------------------------------------------------------------
# the comparison
# ----------------------------------------------------------------------
#: classes whose reference log-probability lies below this are left out
#: of the comparison: a float32 softmax may round them to 0 (e^-87 is
#: float32's smallest normal), and that is no fault of the served net
LOGP_FLOOR = -60.0


def log_softmax(z: np.ndarray) -> np.ndarray:
    z = np.asarray(z, np.float64)
    m = z.max(axis=-1, keepdims=True)
    return z - m - np.log(np.exp(z - m).sum(axis=-1, keepdims=True))


def logit_err(probs: np.ndarray, ref_logits: np.ndarray) -> np.ndarray:
    """Per answer: the relative RMS error of the served logits.

    Served log-probabilities are the logits up to one constant, so a
    softmax that is nearly flat still shows every logit's error.  Over
    the classes whose reference log-probability lies above
    :data:`LOGP_FLOOR`, the served log-probabilities and the reference
    logits each lose their mean; the RMS of the difference is divided by
    the RMS of the centred reference.  ``probs`` are served softmax
    outputs (N, classes); a served 0 or non-finite value among the
    compared classes gives ``inf``.
    """
    ref_logits = np.asarray(ref_logits, np.float64)
    p = np.asarray(probs, np.float64).reshape(ref_logits.shape)
    keep = log_softmax(ref_logits) >= LOGP_FLOOR
    with np.errstate(divide="ignore", invalid="ignore"):
        got = np.log(p)
    out = np.empty(len(p))
    for i, k in enumerate(keep):
        d = got[i, k] - ref_logits[i, k]
        r = ref_logits[i, k] - ref_logits[i, k].mean()
        with np.errstate(invalid="ignore"):
            out[i] = np.sqrt(np.mean((d - d.mean()) ** 2) / np.mean(r * r))
    return np.where(np.isfinite(out), out, np.inf)


def sample(n_done: int, k: int, seed: int) -> List[int]:
    """``k`` of the ``n_done`` finished requests, drawn from ``seed``,
    always with the first and the last one in it."""
    if n_done <= k:
        return list(range(n_done))
    rng = np.random.default_rng([seed, 0x5A3D])
    picked = set(rng.choice(np.arange(1, n_done - 1), size=k - 2,
                            replace=False).tolist())
    return sorted(picked | {0, n_done - 1})

