"""MLP engine — the NeRF MLP and a generic coordinate-MLP (paper §4.3).

The hardware splits the engine into a multi-output network block (MONB — all
hidden layers, 64x64 RMCM sub-MVM tiles) and a single-output network block
(SONB — the output layer, plain MACs). In JAX that boundary is the
``quant``-able hidden matmuls vs. the small exact heads; the 64x64 tiling
itself reappears in the Pallas kernel's BlockSpecs.

Original NeRF network (cfg = NerfConfig): 8x256 trunk with a skip
connection re-injecting the encoded position at layer 4; density head
sigma (1), a 256-d feature, then a 128-wide view-dependent color branch.
~1.19M parameters (paper: "around 1,200,000 parameters of a total size
4.6MB") — small enough to be VMEM/SRAM resident, which is the whole design
premise of the PLCore.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp

from repro.configs.nerf_icarus import NerfConfig
from repro.core import rmcm
from repro.models.params import Decl


# ----------------------------------------------------------- declarations --
def _linear(din: int, dout: int) -> dict:
    return {"w": Decl((din, dout), (None, None)),
            "b": Decl((dout,), (None,), init="zeros")}


def nerf_mlp_decls(cfg: NerfConfig) -> dict:
    W = cfg.trunk_width
    pe, de = cfg.pos_enc_dim, cfg.dir_enc_dim
    trunk = {}
    din = pe
    for i in range(cfg.trunk_layers):
        if i in cfg.skip_at:
            din = W + pe
        trunk[f"l{i}"] = _linear(din, W)
        din = W
    return {
        "trunk": trunk,
        "sigma": _linear(W, 1),            # SONB: density head
        "feat": _linear(W, W),             # bottleneck feature
        "color0": _linear(W + de, cfg.color_width),
        "rgb": _linear(cfg.color_width, 3),  # SONB: color head
    }


def _matmul(x, layer, quant_layer):
    """One linear. quant_layer: RMCM dict for w (paper's MONB path) or None."""
    if quant_layer is not None:
        y = rmcm.rmcm_matmul_ref(x, quant_layer["w"])
    else:
        y = x @ layer["w"]
    return y + layer["b"]


def _slice_q(qw, lo, hi):
    """Row-slice an RMCM weight dict (scale is per-output-column)."""
    return {"mag": qw["mag"][lo:hi], "sign": qw["sign"][lo:hi],
            "scale": qw["scale"]}


def _matmul_split(parts, layer, quant_layer):
    """y = sum_i x_i @ W[rows_i] + b  — identical math to
    concat(x_i) @ W but WITHOUT materializing the concat buffer (a §Perf
    memory-roofline win; broadcasting inputs like a per-ray direction
    encoding stay un-broadcast, e.g. (R,1,de) + (R,N,C) add)."""
    lo = 0
    y = None
    for x in parts:
        hi = lo + x.shape[-1]
        if quant_layer is not None:
            t = rmcm.rmcm_matmul_ref(x, _slice_q(quant_layer["w"], lo, hi))
        else:
            t = x @ layer["w"][lo:hi]
        y = t if y is None else y + t
        lo = hi
    return y + layer["b"]


def nerf_trunk_apply(cfg: NerfConfig, params: dict, pe_pos,
                     quant: Optional[dict] = None):
    """Position-only half of the engine: trunk + density/feature heads.

    (pe_pos (..., pos_enc_dim)) -> (sigma_raw (...,), feat (..., W)).
    Everything view-dependent is downstream (``nerf_color_apply``), which
    makes this output the memoizable unit for cross-ray sample reuse: two
    rays crossing the same quantized position share sigma|feat exactly.
    """
    qt = (quant or {}).get("trunk", {})
    h = pe_pos
    for i in range(cfg.trunk_layers):
        if i in cfg.skip_at:
            # split matmul == concat([h, pe]) @ W without the concat buffer
            h = jax.nn.relu(_matmul_split([h, pe_pos],
                                          params["trunk"][f"l{i}"],
                                          qt.get(f"l{i}")))
        else:
            h = jax.nn.relu(_matmul(h, params["trunk"][f"l{i}"],
                                    qt.get(f"l{i}")))
    sigma = _matmul(h, params["sigma"], None)[..., 0]        # SONB (exact)
    feat = _matmul(h, params["feat"], (quant or {}).get("feat"))
    return sigma, feat


def nerf_color_apply(cfg: NerfConfig, params: dict, feat, pe_dir,
                     quant: Optional[dict] = None):
    """View-dependent color branch: (feat (..., W), pe_dir) -> rgb [0,1]."""
    hc = jax.nn.relu(_matmul_split([feat, pe_dir], params["color0"],
                                   (quant or {}).get("color0")))
    raw = _matmul(hc, params["rgb"], None)                   # SONB (exact)
    return jax.nn.sigmoid(raw)


def nerf_mlp_apply(cfg: NerfConfig, params: dict, pe_pos, pe_dir,
                   quant: Optional[dict] = None):
    """(pe_pos (..., pos_enc_dim), pe_dir (..., dir_enc_dim))
    -> (sigma_raw (...,), rgb (..., 3) in [0,1]).

    ``quant``: optional RMCM-quantized mirror of ``params`` — the hidden
    (MONB) matmuls read approximated weights, heads stay exact, matching
    the MONB/SONB split.

    ``pe_dir`` may be pre-broadcast (..., de) or per-ray (R, 1, de): the
    split color matmul broadcasts it for free (no (T, W+de) concat).
    """
    sigma, feat = nerf_trunk_apply(cfg, params, pe_pos, quant)
    return sigma, nerf_color_apply(cfg, params, feat, pe_dir, quant)


def cone_heads(cfg: NerfConfig, sigma_raw, rgb):
    """The cone path's output activations (Mip-NeRF's): density
    softplus(raw + density_bias), colour padded to
    sigmoid * (1 + 2 p) - p, from the raw density and the sigmoid colour
    that ``nerf_mlp_apply`` (and the kernel's MLP) produce."""
    sigma = jax.nn.softplus(sigma_raw + cfg.density_bias)
    return sigma, rgb * (1.0 + 2.0 * cfg.rgb_padding) - cfg.rgb_padding


# ----------------------------------------------------- generic coordinate MLP
def mlp_decls(in_dim: int, widths: Sequence[int], out_dim: int) -> dict:
    dims = [in_dim, *widths, out_dim]
    return {f"l{i}": _linear(dims[i], dims[i + 1]) for i in range(len(dims) - 1)}


def mlp_apply(params: dict, x, quant: Optional[dict] = None,
              final_activation=None):
    n = len(params)
    for i in range(n):
        x = _matmul(x, params[f"l{i}"], (quant or {}).get(f"l{i}"))
        if i < n - 1:
            x = jax.nn.relu(x)
    return final_activation(x) if final_activation else x
