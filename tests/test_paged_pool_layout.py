"""The head-major K/V pool: ``{"k", "v": [L, N, Hk, bs, D]}`` (int8 scale
planes ``[L, N, Hk, bs]``), read by the paged kernel a head's page as one
whole ``(bs, D)`` tile.

* the kernel against the gather oracle (``_kv_gather`` + ``_masked_sdpa``)
  on head-major pools in every form a cell runs: decode and multi-query,
  full and window-bounded (a ring that wraps), the cells' head shapes
  (``Hk`` 8 with groups of 4, 6 and 9, ``Hk`` 4 with groups of 7), bf16
  and int8, a pool of every layer and one layer's, every position no query
  attends poisoned with NaN;
* ``_kv_store`` then ``_kv_gather``: an entry lands at ``[layer, block,
  head, offset]`` and comes back in logical order;
* ``paged_pool_specs`` shards the head axis, dim 2, under a ``tp_mesh``;
* what a start pays for the kernel: its body, traced at the long-document
  cell's shapes, copies a page with ONE DMA a pool inside the page loop and
  grows with neither the head count nor the page count (a Python loop over
  heads or pages, or a copy a (page, head), would; each is paid again by
  every program that holds the kernel at every warm start).

Interpret mode on the CPU, like every kernel suite here."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.kernels.paged_attention import _tiling, paged_attention
from paddle_tpu.models import generation as G
from paddle_tpu.models.llama import _masked_sdpa

BS, D, M, L = 4, 16, 3, 2


def _oracle(q, pool, layer, tbl, sl, dl, window):
    """Gather + ``_masked_sdpa`` over a LINEAR table: query offset ``i`` of
    slot ``m`` attends ``j <= sl + min(i, dl)`` and, under ``window``, only
    ``j > sl + min(i, dl) - window``."""
    Mq, Q = q.shape[:2]
    Hk = pool["k"].shape[2]
    C = tbl.shape[1] * BS
    kk, vv = G._kv_gather(pool, layer, tbl, Mq, C, Hk, q.shape[-1])
    hi = sl[:, None] + jnp.minimum(jnp.arange(Q)[None, :], dl[:, None])
    j = jnp.arange(C)[None, None, :]
    mask = j <= hi[:, :, None]
    if window is not None:
        mask &= j > hi[:, :, None] - window
    return _masked_sdpa(q.astype(jnp.float32), kk.astype(jnp.float32),
                        vv.astype(jnp.float32), mask)


FORMS = {                      # name: (Q, window)
    "q1": (1, None), "mq": (8, None),
    "window_q1": (1, 12), "window_mq": (8, 12)}
HEADS = [(8, 4), (8, 6), (8, 9), (4, 7)]          # (Hk, G)


# the window-bounded form reads fp pools only (it raises for int8)
KINDS = [(f, k) for f in FORMS for k in ("bf16", "int8")
         if FORMS[f][1] is None or k == "bf16"]


@pytest.mark.parametrize("Hk,grp", HEADS, ids=[f"{h}x{g}" for h, g in HEADS])
@pytest.mark.parametrize("form,kind", KINDS,
                         ids=[f"{f}-{k}" for f, k in KINDS])
def test_head_major_pool_every_form(form, kind, Hk, grp):
    """Each slot attends its own positions through the head-major pool of
    every layer (``layer`` 1) and through that layer alone: both equal the
    gather oracle, and NaN at every position no query attends (blocks no
    table holds, the null block, tails past ``sl + dl``, pages the ring
    has left, positions behind the window, all of layer 0) changes no bit.
    A full row spans two cells of 16 pages; a window row sits deep enough
    that its ring has wrapped twice."""
    Q, window = FORMS[form]
    rng = np.random.default_rng([list(FORMS).index(form), Hk, grp,
                                 kind == "int8"])
    sl = np.array([61, 4, 21], np.int32) if window else \
        np.array([93, 3, 14], np.int32)
    dl = np.array([Q - 1, 0, Q // 2], np.int32)
    pages = (sl + dl) // BS + 1
    Wlin = int(pages.max())
    N = 1 + M * Wlin
    lin = 1 + rng.permutation(M * Wlin).reshape(M, Wlin)
    lin = np.where(np.arange(Wlin)[None, :] < pages[:, None], lin, 0)
    tbl = lin
    if window is not None:                 # page p at entry p % R
        R = (window + Q) // BS + 2
        tbl = np.zeros((M, R), np.int32)
        for m in range(M):
            for p in range(int(pages[m])):
                tbl[m, p % R] = lin[m, p]
        assert pages[0] > 2 * R             # the ring wrapped
    dead = np.ones((N, BS), bool)          # no query of any slot attends
    j = np.arange(Wlin * BS)
    for m in range(M):
        seen = j <= sl[m] + dl[m]
        if window is not None:
            seen &= j > sl[m] - window
        dead[lin[m]] &= ~seen.reshape(Wlin, BS)
    dead[0] = True
    qdt = jnp.bfloat16
    q = jnp.asarray(rng.standard_normal((M, Q, Hk * grp, D)), qdt)
    kv = {n: jnp.asarray(rng.standard_normal((L, N, Hk, BS, D)), jnp.float32)
          for n in ("k", "v")}
    if kind == "int8":
        (kq, ks), (vq, vs) = G._kv_quantize(kv["k"]), G._kv_quantize(kv["v"])
        pool = {"k": kq, "v": vq, "k_scale": ks, "v_scale": vs}
        bad = dict(pool, **{n: jnp.where(dead[None, :, None, :], jnp.nan,
                                         pool[n]).at[0].set(jnp.nan)
                            for n in ("k_scale", "v_scale")})
    else:
        pool = {n: a.astype(jnp.bfloat16) for n, a in kv.items()}
        bad = {n: jnp.where(dead[None, :, None, :, None], jnp.nan,
                            a).at[0].set(jnp.nan)
               for n, a in pool.items()}
    tbl, sl, dl = (jnp.asarray(a, jnp.int32) for a in (tbl, sl, dl))

    def call(p, layer):
        args = dict(k_scale=p.get("k_scale"), v_scale=p.get("v_scale"),
                    window=window)
        if layer is not None:
            args["layer"] = layer
        if Q == 1:
            return paged_attention(q[:, 0], p["k"], p["v"], tbl, sl,
                                   **args)[:, None]
        return paged_attention(q, p["k"], p["v"], tbl, sl, draft_lens=dl,
                               **args)

    out = np.asarray(call(pool, jnp.int32(1)), np.float32)
    want = np.asarray(_oracle(q, pool, 1, jnp.asarray(lin, jnp.int32), sl,
                              dl, window), np.float32)
    real = np.arange(Q)[None, :] <= np.asarray(dl)[:, None]
    tol = 1e-2 if kind == "bf16" else 2e-3   # bf16: the output's rounding
    assert np.isfinite(out).all()
    np.testing.assert_allclose(out[real], want[real], rtol=tol, atol=tol)
    assert not out[~real].any()              # rows past the draft: zeros
    np.testing.assert_array_equal(
        np.asarray(call({n: a[1] for n, a in pool.items()}, None),
                   np.float32), out)         # one layer's pool
    np.testing.assert_array_equal(
        np.asarray(call(bad, jnp.int32(1)), np.float32), out)


@pytest.mark.parametrize("packed", [False, True], ids=["rows", "packed"])
@pytest.mark.parametrize("kv_quant", [None, "int8"])
def test_kv_store_then_gather_round_trip(kv_quant, packed):
    """``_kv_store`` writes an entry's ``Hk`` rows at ``[layer, block,
    head, offset]`` of the head-major pool, a block at a time, and leaves
    every other position of the pool as it was (a block it reads and
    writes back included); a masked entry (``phys`` 0) writes nothing.
    ``_kv_gather`` hands the entries back in logical order ``[B, C, Hk,
    D]`` (an int8 pool's dequantized, which is what the store says later
    reads observe). Two rows of consecutive positions that start and end
    inside blocks, as lanes ``[2, 8]`` or packed into one wave ``[1,
    16]``."""
    from paddle_tpu.models.llama import LlamaConfig
    cfg = LlamaConfig(vocab_size=64, hidden_size=64, intermediate_size=96,
                      num_hidden_layers=3, num_attention_heads=8,
                      num_key_value_heads=4, dtype=jnp.float32)
    Hk, Dh, N, W = cfg.kv_heads, cfg.head_dim, 9, 4
    pool = G.init_paged_pool(cfg, N, BS, kv_quant=kv_quant)
    assert pool["k"].shape == (3, N, Hk, BS, Dh)
    if kv_quant:
        assert pool["k_scale"].shape == (3, N, Hk, BS)
    rng = np.random.default_rng(3)
    pool = {n: jnp.asarray(rng.standard_normal(a.shape) * 50).astype(a.dtype)
            for n, a in pool.items()}
    tbl = jnp.asarray(1 + rng.permutation(8).reshape(2, W), jnp.int32)
    pos = np.array([np.arange(3, 11), np.arange(6, 14)])  # runs, a row
    real = np.ones((2, 8), bool)
    real[1, 6:] = False                                   # masked lanes
    phys = np.where(real, np.take_along_axis(np.asarray(tbl), pos // BS, 1),
                    0)
    off = pos % BS
    k = jnp.asarray(rng.standard_normal((2, 8, Hk, Dh)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((2, 8, Hk, Dh)), jnp.float32)
    lanes = (1, 16) if packed else (2, 8)
    out, ka, va = G._kv_store(
        pool, jnp.int32(1), jnp.asarray(phys.reshape(lanes), jnp.int32),
        jnp.asarray(off.reshape(lanes), jnp.int32),
        k.reshape(lanes + (Hk, Dh)), v.reshape(lanes + (Hk, Dh)), 2)
    ka, va = (np.asarray(x).reshape(2, 8, Hk, Dh) for x in (ka, va))
    for n, x in (("k", ka), ("v", va)):
        got = np.asarray(out[n][1], np.float32)
        if kv_quant:
            got = got * np.asarray(out[n + "_scale"][1])[..., None]
        written = np.zeros((N, BS), bool)
        for b in range(2):
            for t in range(8):
                if not real[b, t]:
                    continue
                written[phys[b, t], off[b, t]] = True
                for h in range(Hk):
                    np.testing.assert_allclose(
                        got[phys[b, t], h, off[b, t]], x[b, t, h],
                        rtol=1e-6, atol=1e-6)
        for leaf in (n, n + "_scale") if kv_quant else (n,):
            before = np.swapaxes(np.asarray(pool[leaf]), 2, 3)  # by cell
            after = np.swapaxes(np.asarray(out[leaf]), 2, 3)
            np.testing.assert_array_equal(after[1][~written],
                                          before[1][~written])
            np.testing.assert_array_equal(after[[0, 2]], before[[0, 2]])
        kk, vv = G._kv_gather(out, 1, tbl, 2, W * BS, Hk, Dh)
        back = np.asarray(kk if n == "k" else vv, np.float32)
        for b in range(2):
            np.testing.assert_allclose(back[b, pos[b][real[b]]],
                                       x[b][real[b]], rtol=1e-6, atol=1e-6)
    if not kv_quant:
        np.testing.assert_array_equal(ka, np.asarray(k))


def test_pool_specs_shard_the_head_axis(tp_platform):
    """Under a ``tp_mesh`` every leaf, K/V and scale planes alike, splits
    dim 2 (the kv heads), and the placed pool holds ``Hk / tp`` heads of
    every block on each device: whole blocks stay axis 1 everywhere."""
    from paddle_tpu.distributed.topology import tp_mesh
    from paddle_tpu.models.llama import LlamaConfig
    cfg = LlamaConfig(vocab_size=64, hidden_size=64, intermediate_size=96,
                      num_hidden_layers=2, num_attention_heads=8,
                      num_key_value_heads=4, dtype=jnp.float32)
    mesh = tp_mesh(2)
    for kv_quant in (None, "int8"):
        pool = G.init_paged_pool(cfg, 5, BS, kv_quant=kv_quant)
        specs = G.paged_pool_specs(pool, mesh)
        for name, spec in specs.items():
            assert tuple(spec)[:3] == (None, None, "tp"), name
        placed = G.init_paged_pool(cfg, 5, BS, kv_quant=kv_quant, mesh=mesh)
        for name, leaf in placed.items():
            shard = leaf.addressable_shards[0].data.shape
            assert shard[2] * 2 == leaf.shape[2] == cfg.kv_heads, name
            assert shard[:2] == leaf.shape[:2] and shard[3:] == \
                leaf.shape[3:], name


# ---------------------------------------------------------------------------
# what a start pays: the kernel's traced body
# ---------------------------------------------------------------------------

def _kernel_jaxpr(Hk, grp, Q, W, window, bs=16, D=128):
    """The kernel body's jaxpr as ``pallas_call`` carries it, for a pool of
    every layer, traced from shapes alone (nothing runs)."""
    q = jax.ShapeDtypeStruct((2, Q, Hk * grp, D) if Q > 1
                             else (2, Hk * grp, D), jnp.bfloat16)
    pool = jax.ShapeDtypeStruct((2, 64, Hk, bs, D), jnp.bfloat16)
    i32 = jax.ShapeDtypeStruct((2,), jnp.int32)

    def f(q, k, v, tbl, sl, dl):
        return paged_attention(q, k, v, tbl, sl,
                               draft_lens=dl if Q > 1 else None,
                               layer=jnp.int32(1), window=window)

    outer = jax.make_jaxpr(f)(q, pool, pool,
                              jax.ShapeDtypeStruct((2, W), jnp.int32),
                              i32, i32)
    (call,) = [e for e in outer.jaxpr.eqns
               if e.primitive.name == "pallas_call"]
    return call.params["jaxpr"]


def _walk(jaxpr, loops=()):
    """Every equation of ``jaxpr`` and of the bodies it holds, with the
    loops around it: ``("scan", length, unroll)`` or ``("while",)``."""
    for e in jaxpr.eqns:
        yield e, loops
        here = loops
        if e.primitive.name == "scan":
            here = loops + (("scan", e.params["length"],
                             e.params["unroll"]),)
        elif e.primitive.name == "while":
            here = loops + (("while",),)
        for v in e.params.values():
            for sub in v if isinstance(v, (list, tuple)) else (v,):
                body = getattr(sub, "jaxpr", sub)
                if hasattr(body, "eqns"):
                    yield from _walk(body, here)


LONGDOC = {"window": (265, 4096), "full": (1024, None)}   # (W, window)


@pytest.mark.parametrize("Q", [1, 128])
@pytest.mark.parametrize("form", list(LONGDOC))
def test_a_start_pays_for_one_kernel_body(form, Q):
    """At the long-document cell's shapes (``Hk`` 4, groups of 7, pages of
    16, ``P`` 16 pages a cell, rings of 265 entries or a table of 1024):
    (a) the pages are copied with ONE DMA start a pool a page, inside the
    page loop and nowhere deeper (two call sites: the first cell's and the
    next cell's), and waited for once a pool a page; (b) every loop over
    pages or heads is ``unrolled()``, a scan whose body is traced once;
    (c) the body does not grow with the head count or the page count.
    Lowering is what a warm start pays for every program that holds the
    kernel, and it unrolls those scans: a copy a (page, head) or a Python
    loop over heads would show here first."""
    W, window = LONGDOC[form]
    P = _tiling(16, W, 7, Q, jnp.bfloat16)[0]
    assert P == 16
    body = _kernel_jaxpr(4, 7, Q, W, window)
    eqns = list(_walk(body))
    for prim, sites in (("dma_start", 4), ("dma_wait", 2)):
        found = [loops for e, loops in eqns if e.primitive.name == prim]
        assert len(found) == sites, prim
        for loops in found:
            scans = [t for t in loops if t[0] == "scan"]
            assert scans == [("scan", P, P)], (prim, loops)
    for e, _ in eqns:
        if e.primitive.name == "scan":
            n = e.params["length"]
            assert n in (P, 4) and e.params["unroll"] == n, e.params
    for Hk, W_other in ((1, W), (8, W), (4, 8)):       # heads; P = 8 pages
        other = list(_walk(_kernel_jaxpr(Hk, 7, Q, W_other, window)))
        assert len(other) == len(eqns), (Hk, W_other)
