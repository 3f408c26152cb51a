"""Diffusion UNet (SDXL-style) — the ppdiffusers capability target.

Capability target: the reference ecosystem's SDXL UNet (ppdiffusers
``models/unet_2d_condition.py``: timestep-embedded ResBlocks,
cross-attention transformer blocks at the lower resolutions, down/up paths
with skip connections; BASELINE.json configs[4] names "SDXL UNet (Pallas
attention)"). This is the architecture at configurable width/depth;
tests train a tiny instance end to end on the epsilon-prediction
objective.

TPU notes: NCHW throughout (the repo's conv convention); attention flattens
spatial to sequence and runs scaled-dot-product attention (the self-attn
at 64x64 latents is S=4096); GroupNorm/SiLU ride XLA fusion.
"""

from __future__ import annotations

import math
from typing import List, Optional, Sequence

import numpy as np
import jax
import jax.numpy as jnp

from ..core.dispatch import forward_op
from ..core.tensor import Tensor
from ..nn import (Conv2D, GroupNorm, Identity, LayerNorm, Linear, SiLU,
                  Sequential)
from ..nn.layer import Layer

__all__ = ["UNet2DConditionModel", "sdxl_unet_mini", "timestep_embedding"]


def timestep_embedding(t, dim: int, max_period: float = 10000.0):
    """Sinusoidal timestep embedding [B] -> [B, dim] (DDPM convention)."""
    def impl(tv):
        half = dim // 2
        freqs = jnp.exp(-math.log(max_period) *
                        jnp.arange(half, dtype=jnp.float32) / half)
        args = tv.astype(jnp.float32)[:, None] * freqs[None]
        return jnp.concatenate([jnp.cos(args), jnp.sin(args)], axis=-1)
    # Tensors pass through unchanged (a to-host round trip would break
    # under a to_static trace); only raw arrays/lists get wrapped.
    return forward_op("timestep_embedding", impl,
                      [t if isinstance(t, Tensor) else
                       __import__("paddle_tpu").to_tensor(np.asarray(t))])


def _groups(c: int, cap: int = 8) -> int:
    """Largest divisor of ``c`` not exceeding ``cap`` (GroupNorm needs
    groups | channels)."""
    for g in range(min(cap, c), 0, -1):
        if c % g == 0:
            return g
    return 1


class ResBlock(Layer):
    """GroupNorm-SiLU-Conv x2 with the timestep embedding added between
    (ref: ppdiffusers ResnetBlock2D)."""

    def __init__(self, cin, cout, temb_dim, groups=8):
        super().__init__()
        self.norm1 = GroupNorm(_groups(cin, groups), cin)
        self.conv1 = Conv2D(cin, cout, 3, padding=1)
        self.temb_proj = Linear(temb_dim, cout)
        self.norm2 = GroupNorm(_groups(cout, groups), cout)
        self.conv2 = Conv2D(cout, cout, 3, padding=1)
        self.act = SiLU()
        self.skip = Conv2D(cin, cout, 1) if cin != cout else Identity()

    def forward(self, x, temb):
        h = self.conv1(self.act(self.norm1(x)))
        from ..ops.manipulation import reshape
        e = self.temb_proj(self.act(temb))
        B, C = e.shape
        h = h + reshape(e, [B, C, 1, 1])
        h = self.conv2(self.act(self.norm2(h)))
        return h + self.skip(x)


class CrossAttnBlock(Layer):
    """LayerNorm'd self-attention + cross-attention + GEGLU-ish FF over the
    flattened spatial sequence (ref: ppdiffusers Transformer2DModel basic
    block, single layer)."""

    def __init__(self, channels, ctx_dim, heads=4):
        super().__init__()
        if channels % heads:
            raise ValueError(f"channels {channels} % heads {heads}")
        self.heads = heads
        self.norm_in = GroupNorm(_groups(channels), channels)
        self.ln1 = LayerNorm(channels)
        self.to_q1 = Linear(channels, channels)
        self.to_k1 = Linear(channels, channels)
        self.to_v1 = Linear(channels, channels)
        self.out1 = Linear(channels, channels)
        self.ln2 = LayerNorm(channels)
        self.to_q2 = Linear(channels, channels)
        self.to_k2 = Linear(ctx_dim, channels)
        self.to_v2 = Linear(ctx_dim, channels)
        self.out2 = Linear(channels, channels)
        self.ln3 = LayerNorm(channels)
        self.ff = Sequential(Linear(channels, 4 * channels), SiLU(),
                             Linear(4 * channels, channels))

    def _attn(self, q, k, v):
        """[B, S, C] x [B, T, C] -> [B, S, C] multi-head SDPA (the flash
        kernel path is used by nn.functional on TPU shapes; the jnp path is
        the oracle on CPU)."""
        from ..nn.functional import scaled_dot_product_attention
        from ..ops.manipulation import reshape
        B, S, C = q.shape
        T = k.shape[1]
        H = self.heads
        D = C // H
        qh = reshape(q, [B, S, H, D])
        kh = reshape(k, [B, T, H, D])
        vh = reshape(v, [B, T, H, D])
        o = scaled_dot_product_attention(qh, kh, vh)
        return reshape(o, [B, S, C])

    def forward(self, x, context):
        from ..ops.manipulation import reshape, transpose
        B, C, H, W = x.shape
        h = self.norm_in(x)
        seq = transpose(reshape(h, [B, C, H * W]), [0, 2, 1])  # [B, S, C]
        a = self.ln1(seq)
        seq = seq + self.out1(self._attn(self.to_q1(a), self.to_k1(a),
                                         self.to_v1(a)))
        a = self.ln2(seq)
        seq = seq + self.out2(self._attn(self.to_q2(a),
                                         self.to_k2(context),
                                         self.to_v2(context)))
        seq = seq + self.ff(self.ln3(seq))
        out = reshape(transpose(seq, [0, 2, 1]), [B, C, H, W])
        return x + out


class Downsample(Layer):
    def __init__(self, c):
        super().__init__()
        self.conv = Conv2D(c, c, 3, stride=2, padding=1)

    def forward(self, x):
        return self.conv(x)


class Upsample(Layer):
    def __init__(self, c):
        super().__init__()
        self.conv = Conv2D(c, c, 3, padding=1)

    def forward(self, x):
        B, C, H, W = x.shape

        def up(v):
            return jax.image.resize(v, (v.shape[0], v.shape[1],
                                        2 * H, 2 * W), method="nearest")
        return self.conv(forward_op("unet_upsample", up, [x]))


class UNet2DConditionModel(Layer):
    """Conditional UNet: eps = f(x_t, t, context).

    ``block_out_channels`` sets the per-level widths; cross-attention runs
    at every level except the first (the SDXL layout: attention at the
    lower spatial resolutions).
    """

    def __init__(self, in_channels: int = 4,
                 block_out_channels: Sequence[int] = (32, 64, 96),
                 ctx_dim: int = 64, heads: int = 4,
                 layers_per_block: int = 1):
        super().__init__()
        chans = list(block_out_channels)
        temb = 4 * chans[0]
        self._temb_base = chans[0]
        self.time_mlp = Sequential(Linear(chans[0], temb), SiLU(),
                                   Linear(temb, temb))
        self.conv_in = Conv2D(in_channels, chans[0], 3, padding=1)

        self.down_res: List = []
        self.down_attn: List = []
        self.downs: List = []
        c = chans[0]
        for li, co in enumerate(chans):
            for bi in range(layers_per_block):
                r = ResBlock(c, co, temb)
                self.add_sublayer(f"dres{li}_{bi}", r)
                self.down_res.append((li, r))
                a = CrossAttnBlock(co, ctx_dim, heads) if li > 0 else None
                if a is not None:
                    self.add_sublayer(f"dattn{li}_{bi}", a)
                self.down_attn.append(a)
                c = co
            if li < len(chans) - 1:
                d = Downsample(co)
                self.add_sublayer(f"down{li}", d)
                self.downs.append(d)

        self.mid1 = ResBlock(c, c, temb)
        self.mid_attn = CrossAttnBlock(c, ctx_dim, heads)
        self.mid2 = ResBlock(c, c, temb)

        self.up_res: List = []
        self.up_attn: List = []
        self.ups: List = []
        for li, co in reversed(list(enumerate(chans))):
            for bi in range(layers_per_block):
                r = ResBlock(c + co, co, temb)   # skip concat
                self.add_sublayer(f"ures{li}_{bi}", r)
                self.up_res.append((li, r))
                a = CrossAttnBlock(co, ctx_dim, heads) if li > 0 else None
                if a is not None:
                    self.add_sublayer(f"uattn{li}_{bi}", a)
                self.up_attn.append(a)
                c = co
            if li > 0:
                u = Upsample(co)
                self.add_sublayer(f"up{li}", u)
                self.ups.append(u)

        self.norm_out = GroupNorm(_groups(c), c)
        self.act = SiLU()
        self.conv_out = Conv2D(c, in_channels, 3, padding=1)

    def forward(self, x, t, context):
        from ..ops.extras import hstack  # noqa: F401 (namespace warm)
        from ..ops.manipulation import concat
        temb = self.time_mlp(timestep_embedding(t, self._temb_base))
        h = self.conv_in(x)
        skips = []
        di = 0
        res_i = 0
        n_levels = (len(self.downs) + 1)
        per = len(self.down_res) // n_levels
        for li in range(n_levels):
            for _ in range(per):
                _, r = self.down_res[res_i]
                h = r(h, temb)
                a = self.down_attn[res_i]
                if a is not None:
                    h = a(h, context)
                skips.append(h)
                res_i += 1
            if li < n_levels - 1:
                h = self.downs[di](h)
                di += 1

        h = self.mid2(self.mid_attn(self.mid1(h, temb), context), temb)

        ui = 0
        res_i = 0
        for li in range(n_levels):
            for _ in range(per):
                _, r = self.up_res[res_i]
                h = r(concat([h, skips.pop()], axis=1), temb)
                a = self.up_attn[res_i]
                if a is not None:
                    h = a(h, context)
                res_i += 1
            if li < n_levels - 1:
                h = self.ups[ui](h)
                ui += 1

        return self.conv_out(self.act(self.norm_out(h)))


def sdxl_unet_mini(**kw) -> UNet2DConditionModel:
    """Test/bench-scale instance of the SDXL layout."""
    return UNet2DConditionModel(**kw)
