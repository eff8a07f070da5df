"""Plain float32 reference of ResNet-50 as the program builds it, and the
benchmark's own weights for it.

He et al. (arXiv:1512.03385), bottleneck variant, NCHW, in ``jax.numpy``
at ``highest`` matmul precision. Departures from the paper, kept because
the program has them: no batch normalisation (each convolution has a
bias instead), the stride of a stage's first unit sits on its first 1x1
convolution, and every convolution pads ``SAME``.

    stem: 7x7/2 conv + ReLU, 2x2/2 max pool
    4 stages of [3, 4, 6, 3] units; unit: 1x1 -> 3x3 -> 1x1 (ReLU after
    the first two), plus a 1x1 projection where width or stride changes,
    ReLU after the sum
    global average pool, fully connected to the classes

Layer index ``i`` is the program's decoupling point ``i``: 0 stem,
1 stem pool, 2.. the units in order (4 is res1_3), then gap, fc.
"""
from __future__ import annotations

import math
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

PLAN = (3, 4, 6, 3)
WIDTHS = (64, 128, 256, 512)


def layers(cfg: Dict[str, Any]) -> List[Tuple[str, str, dict]]:
    """(name, kind, shape facts) of each layer, in order."""
    hw = cfg["image_size"]
    out = [("stem", "conv", dict(cin=3, cout=64, k=7, stride=2, hw=hw))]
    hw //= 2
    out.append(("stem_pool", "pool", dict(hw=hw)))
    hw //= 2
    cin = 64
    for stage, n in enumerate(PLAN):
        cmid = WIDTHS[stage]
        for b in range(n):
            stride = 2 if (b == 0 and stage > 0) else 1
            out.append((f"res{stage + 1}_{b + 1}", "unit",
                        dict(cin=cin, cmid=cmid, cout=4 * cmid,
                             stride=stride, hw=hw)))
            hw //= stride
            cin = 4 * cmid
    out.append(("gap", "gap", {}))
    out.append(("fc", "fc", dict(fin=cin, fout=cfg["num_classes"])))
    return out


def param_layout(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """(init, shape, fan-in) of every weight, in the program's tree."""
    tree: Dict[str, Any] = {}
    for name, kind, f in layers(cfg):
        if kind == "conv":
            tree[name] = {"w": ("he", (f["cout"], f["cin"], f["k"], f["k"]),
                                f["cin"] * f["k"] ** 2),
                          "b": ("zeros", (f["cout"],))}
        elif kind == "unit":
            ci, cm, co = f["cin"], f["cmid"], f["cout"]
            p = {"w1": ("he", (cm, ci, 1, 1), ci),
                 "w2": ("he", (cm, cm, 3, 3), cm * 9),
                 "w3": ("normal", (co, cm, 1, 1), cm),
                 "b1": ("zeros", (cm,)), "b2": ("zeros", (cm,)),
                 "b3": ("zeros", (co,))}
            if ci != co or f["stride"] != 1:
                p["wp"] = ("normal", (co, ci, 1, 1), ci)
            tree[name] = p
        elif kind == "fc":
            tree[name] = {"w": ("normal", (f["fin"], f["fout"]), f["fin"]),
                          "b": ("zeros", (f["fout"],))}
        else:
            tree[name] = {}
    return tree


def _is_leaf(x) -> bool:
    return isinstance(x, tuple) and isinstance(x[0], str)


def make_params(cfg: Dict[str, Any], key, dtype=None):
    """Random weights from ``key`` in one jitted call, on the device:
    He-normal before a ReLU, normal / sqrt(fan-in) elsewhere, zero
    biases."""
    layout = param_layout(cfg)
    leaves, treedef = jax.tree.flatten(layout, is_leaf=_is_leaf)
    dt = jnp.dtype(dtype or cfg["param_dtype"])

    def build(k):
        keys = jax.random.split(k, len(leaves))
        vals = []
        for kk, (init, shape, *fan) in zip(keys, leaves):
            if init == "zeros":
                vals.append(jnp.zeros(shape, dt))
                continue
            gain = 2.0 if init == "he" else 1.0
            std = math.sqrt(gain / fan[0])
            vals.append((jax.random.normal(kk, shape, jnp.float32)
                         * std).astype(dt))
        return jax.tree.unflatten(treedef, vals)

    return jax.jit(build)(key)


def make_images(cfg: Dict[str, Any], key, n: int, dtype=jnp.float32):
    """``n`` standard-normal images (n, 3, H, W), one jitted call."""
    s = cfg["image_size"]
    return jax.jit(lambda k: jax.random.normal(k, (n, 3, s, s), dtype))(key)


def _bf16(x):
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def three_pass(op):
    """``op`` (a bilinear map) as three bfloat16 passes, the way a TPU
    computes float32 at ``high`` precision: each operand split into a
    bfloat16 high part and a bfloat16 remainder, the product of the two
    remainders left out."""
    def run(x, w):
        xh, wh = _bf16(x), _bf16(w)
        xl, wl = _bf16(x - xh), _bf16(w - wh)
        return op(xh, wh) + op(xh, wl) + op(xl, wh)
    return run


def _conv_op(stride):
    return lambda x, w: jax.lax.conv_general_dilated(
        x, w, (stride, stride), "SAME",
        dimension_numbers=("NCHW", "OIHW", "NCHW"))


def _apply(kind, f, p, x, passes=0):
    def _conv(x, w, b, stride):
        op = _conv_op(stride)
        y = (three_pass(op) if passes == 3 else op)(x, w)
        return y + b[None, :, None, None]

    if kind == "conv":
        return jax.nn.relu(_conv(x, p["w"], p["b"], f["stride"]))
    if kind == "pool":
        return jax.lax.reduce_window(x, -jnp.inf, jax.lax.max,
                                     (1, 1, 2, 2), (1, 1, 2, 2), "VALID")
    if kind == "unit":
        s = f["stride"]
        h = jax.nn.relu(_conv(x, p["w1"], p["b1"], s))
        h = jax.nn.relu(_conv(h, p["w2"], p["b2"], 1))
        h = _conv(h, p["w3"], p["b3"], 1)
        if "wp" in p:
            zero = jnp.zeros((h.shape[1],), h.dtype)
            x = _conv(x, p["wp"], zero, s)
        return jax.nn.relu(h + x)
    if kind == "gap":
        return x.mean(axis=(2, 3))
    mm = three_pass(jnp.matmul) if passes == 3 else jnp.matmul
    return mm(x, p["w"]) + p["b"]


def forward(cfg: Dict[str, Any], params, x, start: int = 0,
            stop: Optional[int] = None, passes: int = 0,
            precision: str = "highest"):
    """Layers ``start`` .. ``stop - 1`` (all by default) on ``x``, in
    float32: ``forward(.., stop=cut + 1)`` is the head up to a cut,
    ``forward(.., start=cut + 1)`` the tail after it, giving logits.
    A control lowers the precision: ``precision="high"`` is a TPU's own
    three bfloat16 passes; ``passes=3`` writes them out, for a backend
    that computes every float32 precision alike (the CPU)."""
    with jax.default_matmul_precision(precision):
        x = x.astype(jnp.float32)
        for name, kind, f in layers(cfg)[start:stop]:
            x = _apply(kind, f, params[name], x, passes)
        return x


def image_flops(cfg: Dict[str, Any]) -> float:
    """Model FLOPs of one image: 2 per multiply-accumulate of every
    convolution and of the classifier."""
    macs = 0.0
    for _, kind, f in layers(cfg):
        if kind == "conv":
            out = f["hw"] // f["stride"]
            macs += out * out * f["cout"] * f["cin"] * f["k"] ** 2
        elif kind == "unit":
            out = f["hw"] // f["stride"]
            ci, cm, co = f["cin"], f["cmid"], f["cout"]
            macs += out * out * (cm * ci + cm * cm * 9 + co * cm)
            if ci != co or f["stride"] != 1:
                macs += out * out * co * ci
        elif kind == "fc":
            macs += f["fin"] * f["fout"]
    return 2.0 * macs
