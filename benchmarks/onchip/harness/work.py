"""Operations and compulsory bytes of a conv network, from the layer
table of its configuration file alone.

Every roofline and `mfu` number of the benchmark divides by these, so
a change to the program's planner, tiling or fusion is judged against
the same work.  A layer row has `ci`, `co`, `k`, `stride`, `pad`, the
input plane `h`, `w`, the effective `pool` after it, an optional
`residual` edge and optional `groups` (1 where absent: each output
channel reduces over `ci / groups` input channels).  Word size is that
of the configuration's `dtype`.  These are the default work counts; a
configuration with passes other than convolutions counts its own
(`harness/parts.py`).
"""

from __future__ import annotations

WORD_BYTES = {"float32": 4, "bfloat16": 2, "float16": 2}


def out_plane(layer: dict) -> tuple[int, int]:
    """Conv output plane (before the pool)."""
    k, s, p = layer["k"], layer["stride"], layer["pad"]
    return ((layer["h"] + 2 * p - k) // s + 1,
            (layer["w"] + 2 * p - k) // s + 1)


def kernel_words(layer: dict) -> int:
    """Words of one conv layer's kernel, (k, k, ci / groups, co)."""
    return layer["k"] ** 2 * (layer["ci"] // layer.get("groups", 1)) \
        * layer["co"]


def conv_macs(layer: dict) -> int:
    """Multiply-accumulates of one image through one conv layer."""
    ho, wo = out_plane(layer)
    return ho * wo * kernel_words(layer)


def head_macs(cfg: dict) -> int:
    return cfg["layers"][-1]["co"] * cfg["classes"]


def forward_flops(cfg: dict) -> float:
    """FLOPs of one image's forward pass: every conv and the head."""
    return 2.0 * (sum(conv_macs(l) for l in cfg["layers"]) + head_macs(cfg))


def train_flops(cfg: dict) -> float:
    """FLOPs one training image requires: forward, weight gradient of
    every layer and the head, and the input gradient of every layer
    but the first (nothing consumes the image's gradient)."""
    layers = cfg["layers"]
    fwd = forward_flops(cfg)
    dgrad = 2.0 * (sum(conv_macs(l) for l in layers[1:]) + head_macs(cfg))
    return 2 * fwd + dgrad


def _words(layer: dict, batch: int) -> dict[str, int]:
    ho, wo = out_plane(layer)
    pool = layer.get("pool", 1)
    return {"x": batch * layer["h"] * layer["w"] * layer["ci"],
            "w": kernel_words(layer),
            "b": layer["co"],
            "y": batch * ho * wo * layer["co"],
            "y_pooled": batch * (ho // pool) * (wo // pool) * layer["co"]}


def pass_work(cfg: dict, batch: int, passes=("fwd",)) -> list[dict]:
    """Per layer and pass: FLOPs and compulsory HBM bytes at `batch`.

    fwd reads x, w, bias (and the residual) and writes the pooled
    output; dgrad reads dy and w and writes dx; wgrad reads x and dy
    and writes dw.  The first layer has no dgrad."""
    wb = WORD_BYTES[cfg["dtype"]]
    out = []
    for i, layer in enumerate(cfg["layers"]):
        n = _words(layer, batch)
        flops = 2.0 * batch * conv_macs(layer)
        res = n["y"] if layer.get("residual") else 0
        words = {"fwd": n["x"] + n["w"] + n["b"] + res + n["y_pooled"],
                 "dgrad": n["y"] + n["w"] + n["x"],
                 "wgrad": n["x"] + n["y"] + n["w"]}
        for p in passes:
            if p == "dgrad" and i == 0:
                continue
            out.append({"layer": layer["name"], "pass": p,
                        "flops": flops, "bytes": words[p] * wb})
    return out


def roofline_seconds(work: list[dict], peak: dict) -> float:
    """Least time the chip could take for these passes, each bounded
    by the larger of its operations and its bytes."""
    return sum(max(w["flops"] / peak["flops_per_s"],
                   w["bytes"] / peak["hbm_bytes_per_s"]) for w in work)
