"""chip_smoke.py stays runnable without a chip (ISSUE 21).

The script itself has no CPU mode and must refuse to start here; what
tier-1 can hold is everything around the device: its stage functions at
toy size on the virtual CPU mesh (kernels in Pallas interpret mode, the
``tp=2`` and ``dp=2 x mp=2`` legs on the virtual devices), every Pallas
kernel pushed through the TPU lowering at the chip's real shapes, and the
compile-cache contract (``JAX_COMPILATION_CACHE_DIR`` survives ``import
paddle_tpu``; unset, the one resolver answers ``<checkout>/.jax_cache``).
"""

import json
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke as cs  # noqa: E402
from paddle_tpu.inference.serving import ServingConfig  # noqa: E402
from paddle_tpu.models.llama import LlamaConfig  # noqa: E402

TOY = LlamaConfig(vocab_size=128, hidden_size=64, intermediate_size=128,
                  num_hidden_layers=2, num_attention_heads=4,
                  num_key_value_heads=4, max_position_embeddings=64,
                  use_kernels=True, remat=True, dtype=jnp.bfloat16,
                  param_dtype=jnp.float32)
# the chip's ServingConfig() with every size shrunk; "on" because the
# platform-resolved "auto" is the gather path off the chip
TOY_SC = dict(block_size=4, max_slots=2, max_model_len=32, prefill_chunk=8,
              paged_kernel="on")
TOY_TRAFFIC = dict(lengths=(4, 9, 18, 26), prefix=8, new_tokens=(2, 4))


@pytest.fixture(scope="module")
def clock():
    return cs.CompileClock()


def _rows(capsys):
    return {r["stage"]: r for r in map(json.loads,
                                       capsys.readouterr().out.splitlines())}


class TestStagesAtToySize:
    # one kernel of each family through the whole compile-run-compare
    # path (varlen flash covers the causal and segment gates; the int8
    # GQA multi-query and the NaN-poisoned fp decode are the paged
    # kernel's two extremes); the other cases only trace
    RUN = ("flash_attention varlen fwd+bwd",
           "paged_attention int8 pool 8/1 heads Q=8",
           "paged_attention fp pool 4/4 heads Q=1",
           "paged_attention whole fp pool layer 1 of 2, 8/1 heads Q=8",
           "quant_matmul M=8", "rms_norm fwd+bwd", "apply_rope")

    def test_kernel_rollcall(self, monkeypatch):
        sc = ServingConfig(**TOY_SC)
        cases = cs.kernel_cases(TOY, 2, 16, sc)
        names = [c[0] for c in cases]
        assert len(set(names)) == 16 and set(self.RUN) <= set(names)
        for family, n in (("flash_attention", 3), ("paged_attention", 10),
                          ("quant_matmul", 1), ("rms_norm", 1),
                          ("apply_rope", 1)):
            assert sum(k.startswith(family) for k in names) == n
        for name, fn, ref, build in cases:
            args = jax.eval_shape(build)
            got, want = jax.eval_shape(fn, *args), jax.eval_shape(ref, *args)
            assert jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), got) \
                == jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), want)
        monkeypatch.setattr(cs, "kernel_cases", lambda *a: [
            c for c in cases if c[0] in self.RUN])
        rows = cs.kernel_rollcall(TOY, 2, 16, sc, interpret=True)
        assert [r["kernel"] for r in rows] == [n for n in names
                                               if n in self.RUN]
        assert all(r["err_over_tol"] <= 1.0 for r in rows)
        with pytest.raises(AssertionError, match="interpret"):
            cs.kernel_rollcall(TOY, 2, 16, sc, interpret=False)

    def test_trainer_dp2_mp2(self, clock, tp_platform):
        """The sharded leg: the flash kernel as a per-shard region under
        the mesh, AdamW state laid out like its parameters — one
        compilation, shards on all four devices."""
        row = cs.trainer(clock, TOY, 4, 16, 3, dp=2, mp=2)
        assert row["losses"][-1] < row["losses"][0]
        assert row["param_shard_devices"] == [0, 1, 2, 3]
        assert row["compilations_after_first_step"] == 0
        assert row["donated"] is False          # the CPU has no donation

    def test_servers_and_oracle_tp2(self, clock, capsys, tp_platform):
        sc = ServingConfig(tp=2, **TOY_SC)
        int8 = ServingConfig(kv_quant="int8", quantize="int8", **TOY_SC)
        cs.server_stages(clock, TOY, sc, int8, label="_tp2", **TOY_TRAFFIC)
        rows = _rows(capsys)
        srv = rows["server_tp2"]
        assert srv["platform"] == "cpu" and srv["device_count"] >= 2
        assert srv["paged_kernel"] is True and srv["tp_degree"] == 2
        assert srv["restarts"] == 0 and srv["blocks_in_use"] == 0
        assert srv["mixed_dispatches"] >= 1 and srv["prefix_hit_tokens"] > 0
        assert rows["server_int8_tp2"]["kv_quant"] == "int8"
        oracle = rows["oracle_tp2"]
        assert len(oracle["bf16_worst_gap"]) == 4
        assert len(oracle["int8_worst_gap"]) == 1


def test_oracle_catches_a_wrong_token():
    """The tolerance must be tight enough to fail on garbage: teacher-force
    a stream whose tokens were NOT chosen by the model."""
    import numpy as np
    from paddle_tpu.models import llama
    params = llama.init_params(TOY, jax.random.PRNGKey(3))
    prompt = np.arange(1, 9, dtype=np.int32)
    with pytest.raises(AssertionError, match="logit oracle"):
        cs.logit_oracle(params, TOY, [(prompt, [5, 6, 7, 8])], 1e-3)


def test_refuses_to_start_without_a_chip():
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        capture_output=True, text=True, cwd=REPO, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode != 0
    assert "'cpu'" in proc.stderr and "no CPU mode" in proc.stderr
    assert '"ok"' not in proc.stdout


class TestCompileCachePlacement:
    def test_env_dir_survives_import(self, tmp_path):
        """``import paddle_tpu`` neither sets nor clears the cache dir,
        and the resolver yields to the environment."""
        code = ("import jax, paddle_tpu\n"
                "from paddle_tpu.jit import enable_compile_cache\n"
                "print(jax.config.jax_compilation_cache_dir)\n"
                "print(enable_compile_cache())\n"
                "print(jax.config.jax_compilation_cache_dir)\n")
        d = str(tmp_path / "cc")
        proc = subprocess.run(
            [sys.executable, "-c", code], capture_output=True, text=True,
            cwd=REPO, timeout=120,
            env={**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": REPO,
                 "JAX_COMPILATION_CACHE_DIR": d})
        assert proc.returncode == 0, proc.stderr
        assert proc.stdout.split() == [d, d, d]

    def test_unset_resolves_to_checkout(self, monkeypatch,
                                        compile_cache_config_restored):
        from paddle_tpu.jit import enable_compile_cache
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        assert enable_compile_cache() == os.path.join(REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == \
            os.path.join(REPO, ".jax_cache")


def _preset_cases(monkeypatch):
    """Every roll-call kernel at the chip's real shapes, with the ONE
    dispatch gate saying 'TPU' so nothing lowers in interpret mode."""
    from paddle_tpu.kernels import dispatch
    monkeypatch.setattr(dispatch, "on_tpu", lambda: True)
    assert dispatch.interpret() is False
    cfg, batch, seq = cs.preset()
    return cs.kernel_cases(cfg, batch, seq, ServingConfig())


def _sat_cell_cases(cases, more_chunks=(), shards=(1,)):
    """The paged kernel at the shapes the benchmark's serving cell runs
    (32 slots, GQA 32/8 heads of 128, 256 pages of 16, bf16), read from
    the cell's own configuration: the decode step and the mixed step at
    the cell's ``prefill_chunk`` and at ``more_chunks``; for ``shards``
    past 1, one TP shard's heads of the mixed step, on a bf16 and an int8
    pool (at 8 shards a pool holds ONE kv head: a page of one ``(bs, D)``
    tile, which the kernel copies whole)."""
    with open(os.path.join(REPO, "benchmark", "configs",
                           "mistral-7b-v0.3-d16.json")) as f:
        c = json.load(f)
    eng = c["engine"]
    H, Hk = c["num_attention_heads"], c["num_key_value_heads"]
    bs, M = eng["block_size"], eng["max_slots"]
    chunks = (eng["prefill_chunk"],) + tuple(more_chunks)
    paged = next(fn for name, fn, _, _ in cases
                 if name.startswith("paged_attention"))
    key = jax.random.key(0, impl="rbg")
    return [(f"paged_attention sat cell {H // tp}/{Hk // tp} heads Q={Q} "
             f"{'int8' if quant else 'bf16'}", paged, None,
             lambda Q=Q, tp=tp, quant=quant: (cs._paged_operands(
                 key, M, Q, H // tp, Hk // tp, c["hidden_size"] // H, bs,
                 eng["max_model_len"] // bs, quant, jnp.bfloat16),))
            for tp in shards
            for quant in ((False, True) if tp > 1 else (False,))
            for Q in ((1,) + chunks if tp == 1 else chunks[:1])]


def _sat_cell_mixed_step_case(layers=1):
    """The PACKED ``paged_mixed_step`` (ISSUE 28) around that kernel, at
    the serving cell's shapes: 32 slots x a 128-token chunk, the pool's
    3072 blocks, the cell's widths, ``layers`` of its 16 layers. What the
    waves add to the program (a loop around the layer scan, the row
    view's slices and gathers) meets the TPU's compiler here."""
    from paddle_tpu.models import generation as G
    from paddle_tpu.models.llama import LlamaConfig, init_params
    with open(os.path.join(REPO, "benchmark", "configs",
                           "mistral-7b-v0.3-d16.json")) as f:
        c = json.load(f)
    eng = c["engine"]
    cfg = LlamaConfig(
        **{k: c[k] for k in (
            "vocab_size", "hidden_size", "intermediate_size",
            "num_attention_heads", "num_key_value_heads", "rms_norm_eps",
            "rope_theta")}, num_hidden_layers=layers,
        max_position_embeddings=eng["max_model_len"], dtype=jnp.bfloat16,
        param_dtype=jnp.bfloat16)
    M, Q, bs = eng["max_slots"], eng["prefill_chunk"], eng["block_size"]

    def build():
        i32 = jnp.int32
        return ({"params": init_params(cfg, jax.random.key(0, impl="rbg")),
                 "pool": G.init_paged_pool(cfg, eng["num_blocks"], bs),
                 "tokens": jnp.zeros((M, Q), i32),
                 "starts": jnp.zeros((M,), i32),
                 "q_lens": jnp.ones((M,), i32),
                 "tables": jnp.zeros((M, eng["max_model_len"] // bs), i32),
                 "active": jnp.ones((M,), bool)},)

    def step(o):
        return G.paged_mixed_step(
            o["params"], cfg, o["tokens"], o["starts"], o["q_lens"],
            o["tables"], o["pool"], o["active"], use_kernel=True)

    return (f"paged_mixed_step sat cell packed {M}x{Q}, {layers} layer(s)",
            step, None, build)


def test_every_kernel_lowers_for_tpu(monkeypatch):
    """The check that found ISSUE 21's refused paged-attention BlockSpecs
    from a machine with no chip: the Pallas TPU lowering runs in
    ``jax.export`` and rejects a tile the compiler cannot take."""
    cases = _preset_cases(monkeypatch)
    for name, fn, _ref, build in (cases + _sat_cell_cases(cases) +
                                  [_sat_cell_mixed_step_case()]):
        exported = jax.export.export(jax.jit(fn), platforms=["tpu"])(
            *jax.eval_shape(build))
        assert "tpu_custom_call" in exported.mlir_module(), name


@pytest.mark.slow
def test_every_kernel_compiles_for_v5e_without_a_chip(monkeypatch):
    """Stronger than the lowering guard, and slower (~1 min): libtpu can
    describe a v5e topology with no chip attached, and compiling against
    it runs the whole Mosaic pipeline — vector layouts, VMEM limits —
    exactly as the chip's compiler will."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(topology_name="v5e:2x2",
                                            platform="tpu")
    except Exception as e:                    # noqa: BLE001 — no libtpu here
        pytest.skip(f"no TPU topology without a chip: {e}")
    where = SingleDeviceSharding(topo.devices[0])
    # the chip runs at jax's own default matmul precision, not conftest's
    # "highest" — under which the flash backward kernel's 1024x1024 tiles
    # outgrow the 16 MiB scoped-VMEM default (17.4 MiB asked)
    # beside the cell's chunk (128), ServingConfig()'s default 256, which
    # the compiler refused before the query tile stopped growing with Q
    # (PERF.md, PR 23 finding 2)
    cases = _preset_cases(monkeypatch)
    with jax.default_matmul_precision("default"):
        for name, fn, _ref, build in cases + _sat_cell_cases(
                cases, more_chunks=(256,), shards=(1, 2, 4, 8)) + [
                    _sat_cell_mixed_step_case()]:
            args = jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                               sharding=where),
                jax.eval_shape(build))
            jax.jit(fn).lower(*args).compile()
        # ISSUE 30: the pool is loop state of ONE buffer. With the pool
        # donated, as the engine donates it, the packed step over two
        # layers holds no copy of the pool and never materialises a
        # layer's slab (until then: two ``copy`` of the whole pool a wave,
        # a ``dynamic-slice`` and a write-back of 100 MB a layer)
        _, step, _, build = _sat_cell_mixed_step_case(layers=2)
        (o,) = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=where),
            jax.eval_shape(build))
        pool = o.pop("pool")
        text = jax.jit(lambda pool, o: step({**o, "pool": pool}),
                       donate_argnums=0).lower(pool, o).compile().as_text()
    whole = "bf16[%s]" % ",".join(map(str, pool["k"].shape))
    slab = "bf16[%s]" % ",".join(map(str, pool["k"].shape[1:]))
    assert f" = {whole}{{" in text               # the pool is in the text
    ops = re.findall(r" = (bf16\[[\d,]+\])\{[^}]*\} ([\w\-]+)\(", text)
    assert (whole, "copy") not in ops
    assert not [op for shape, op in ops if shape == slab]


def _moe_cell_cases():
    """The two kernels the latent-attention family with routed experts
    brought, at the shapes its serving cell runs, read from the cell's own
    configuration: the latent paged attention at a decode step's lanes
    (one a slot) and at one wave of a packed mixed step, and both grouped
    matmuls of the held experts at the (token, pick) pairs of each."""
    from paddle_tpu.kernels import grouped_matmul, paged_attention_latent
    from paddle_tpu.models.pangu_ultra_moe import _WAVE_ROWS
    with open(os.path.join(REPO, "benchmark", "configs",
                           "openpangu-ultra-moe-718b-ep16-d5.json")) as f:
        c = json.load(f)
    eng = c["engine"]
    H, R, dr = (c["num_attention_heads"], c["kv_lora_rank"],
                c["qk_rope_head_dim"])
    E, I, held = c["hidden_size"], c["moe_intermediate_size"], \
        c["n_routed_experts"]
    M, bs, W = (eng["max_slots"], eng["block_size"],
                eng["max_model_len"] // eng["block_size"])
    D = -(-(R + dr) // 128) * 128
    bf, i32 = jnp.bfloat16, jnp.int32
    cases = []
    for T in (M, _WAVE_ROWS * M):
        cases.append((
            f"paged_attention_latent {T} lanes",
            lambda ql, qr, pool, tbl, slot, lens: paged_attention_latent(
                ql, qr, pool, jnp.int32(2), tbl, slot, lens, 192 ** -0.5),
            lambda T=T: (jnp.zeros((T, H, R), bf), jnp.zeros((T, H, dr), bf),
                         jnp.zeros((c["num_hidden_layers"],
                                    eng["num_blocks"], bs, D), bf),
                         jnp.zeros((M, W), i32), jnp.zeros((T,), i32),
                         jnp.zeros((T,), i32))))
        rows = T * c["num_experts_per_tok"]
        for K, N, gated in ((E, 2 * I, True), (I, E, False)):
            cases.append((
                f"moe_grouped_matmul {rows} rows {K}x{N}",
                # the expert layers' weights stacked, one layer read in place
                lambda x, w, g, gated=gated: grouped_matmul(
                    x, w, g, layer=jnp.int32(2), gated=gated,
                    use_kernel=True),
                lambda rows=rows, K=K, N=N: (
                    jnp.zeros((rows, K), bf),
                    jnp.zeros((c["num_hidden_layers"] -
                               c["first_k_dense_replace"], held, K, N), bf),
                    jnp.zeros((held,), i32))))
    return cases


MOE_CELL_CASES = ["paged_attention_latent 64 lanes",
                  "moe_grouped_matmul 512 rows 7680x4096",
                  "moe_grouped_matmul 512 rows 2048x7680",
                  "paged_attention_latent 256 lanes",
                  "moe_grouped_matmul 2048 rows 7680x4096",
                  "moe_grouped_matmul 2048 rows 2048x7680"]


@pytest.mark.parametrize("case", MOE_CELL_CASES)
def test_latent_and_grouped_kernels_lower_for_tpu(case, monkeypatch):
    """The latent paged attention and the grouped matmul through the Pallas
    TPU lowering at the serving cell's shapes."""
    from paddle_tpu.kernels import dispatch
    monkeypatch.setattr(dispatch, "on_tpu", lambda: True)
    cases = {name: (fn, build) for name, fn, build in _moe_cell_cases()}
    assert list(cases) == MOE_CELL_CASES
    fn, build = cases[case]
    exported = jax.export.export(jax.jit(fn), platforms=["tpu"])(
        *jax.eval_shape(build))
    text = exported.mlir_module()
    assert "tpu_custom_call" in text, case
    assert case.split()[0] in text        # the name a device trace shows


@pytest.mark.slow
def test_latent_and_grouped_kernels_compile_for_v5e_without_a_chip(
        monkeypatch):
    """The same through libtpu's whole compiler for a described v5e: what
    found that a kernel's own copy cannot slice a 576-lane page (the
    latent pool is 640 wide for it)."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from paddle_tpu.kernels import dispatch
    try:
        topo = topologies.get_topology_desc(topology_name="v5e:2x2",
                                            platform="tpu")
    except Exception as e:                    # noqa: BLE001 — no libtpu here
        pytest.skip(f"no TPU topology without a chip: {e}")
    monkeypatch.setattr(dispatch, "on_tpu", lambda: True)
    where = SingleDeviceSharding(topo.devices[0])
    with jax.default_matmul_precision("default"):
        for name, fn, build in _moe_cell_cases():
            args = jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                               sharding=where),
                jax.eval_shape(build))
            jax.jit(fn).lower(*args).compile()


def _window_cell_cases():
    """What the family with window and full attention layers brought, at
    the shapes its serving cell runs, read from the cell's own
    configuration: the window-bounded paged kernel on a group's ring
    (query groups of 9 a kv head) and the paged kernel the dense family
    calls at this family's full layers (groups of 6, a table of 1024
    pages), each at a decode step and at a chunk row; and both grouped
    matmuls of the held experts (3072 x 1024) at the (token, pick) pairs
    of a decode step and of one wave of a packed mixed step."""
    from paddle_tpu.kernels import grouped_matmul, paged_attention
    from paddle_tpu.models.laguna import _WAVE_ROWS
    with open(os.path.join(REPO, "benchmark", "configs",
                           "laguna-s-2.1-ep8-d9.json")) as f:
        c = json.load(f)
    eng = c["engine"]
    heads = dict(zip(c["layer_types"], c["num_attention_heads_per_layer"]))
    Hk, D = c["num_key_value_heads"], c["head_dim"]
    M, bs, chunk = eng["max_slots"], eng["block_size"], eng["prefill_chunk"]
    window = c["sliding_window"]
    ring = -(-(window + chunk) // bs) + 1
    group = c["layer_types"][:c["num_hidden_layers"]].count("full_attention")
    E, I, held = c["hidden_size"], c["moe_intermediate_size"], \
        c["num_experts"]
    bf, i32 = jnp.bfloat16, jnp.int32
    cases = []
    for kind, W, win in (("sliding_attention", ring, window),
                         ("full_attention", eng["max_model_len"] // bs,
                          None)):
        for Q in (1, chunk):
            name = (f"paged_attention{'_window' if win else ''}_"
                    f"{'q1' if Q == 1 else 'mq'} {heads[kind]}/{Hk} heads")

            def fn(q, k, v, tbl, sl, dl, Q=Q, win=win):
                if Q == 1:
                    return paged_attention(q[:, 0], k, v, tbl, sl,
                                           layer=jnp.int32(1), window=win)
                return paged_attention(q, k, v, tbl, sl, draft_lens=dl,
                                       layer=jnp.int32(1), window=win)

            cases.append((name, fn, lambda Q=Q, W=W, kind=kind: (
                jnp.zeros((M, Q, heads[kind], D), bf),
                jnp.zeros((group, eng["num_blocks"], Hk, bs, D), bf),
                jnp.zeros((group, eng["num_blocks"], Hk, bs, D), bf),
                jnp.zeros((M, W), i32), jnp.zeros((M,), i32),
                jnp.zeros((M,), i32))))
    for T in (M, _WAVE_ROWS * M):
        rows = T * c["num_experts_per_tok"]
        for K, N, gated in ((E, 2 * I, True), (I, E, False)):
            cases.append((
                f"moe_grouped_matmul {rows} rows {K}x{N}",
                lambda x, w, g, gated=gated: grouped_matmul(
                    x, w, g, layer=jnp.int32(2), gated=gated,
                    use_kernel=True),
                lambda rows=rows, K=K, N=N: (
                    jnp.zeros((rows, K), bf),
                    jnp.zeros((6, held, K, N), bf),
                    jnp.zeros((held,), i32))))
    return cases


WINDOW_CELL_CASES = ["paged_attention_window_q1 72/8 heads",
                     "paged_attention_window_mq 72/8 heads",
                     "paged_attention_q1 48/8 heads",
                     "paged_attention_mq 48/8 heads",
                     "moe_grouped_matmul 320 rows 3072x2048",
                     "moe_grouped_matmul 320 rows 1024x3072",
                     "moe_grouped_matmul 10240 rows 3072x2048",
                     "moe_grouped_matmul 10240 rows 1024x3072"]


@pytest.mark.parametrize("case", WINDOW_CELL_CASES)
def test_window_and_grouped_kernels_lower_for_tpu(case, monkeypatch):
    """Both forms of the window-bounded paged kernel, the full layers'
    calls and the grouped matmul at 3072 x 1024 through the Pallas TPU
    lowering at the serving cell's shapes."""
    from paddle_tpu.kernels import dispatch
    monkeypatch.setattr(dispatch, "on_tpu", lambda: True)
    cases = {name: (fn, build) for name, fn, build in _window_cell_cases()}
    assert list(cases) == WINDOW_CELL_CASES
    fn, build = cases[case]
    exported = jax.export.export(jax.jit(fn), platforms=["tpu"])(
        *jax.eval_shape(build))
    text = exported.mlir_module()
    assert "tpu_custom_call" in text, case
    assert case.split()[0] in text        # the name a device trace shows


@pytest.mark.slow
def test_window_and_grouped_kernels_compile_for_v5e_without_a_chip(
        monkeypatch):
    """The same through libtpu's whole compiler for a described v5e (a
    table of 1024 pages a slot in scalar memory, row offsets divided by a
    group of 6 or 9)."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from paddle_tpu.kernels import dispatch
    try:
        topo = topologies.get_topology_desc(topology_name="v5e:2x2",
                                            platform="tpu")
    except Exception as e:                    # noqa: BLE001 — no libtpu here
        pytest.skip(f"no TPU topology without a chip: {e}")
    monkeypatch.setattr(dispatch, "on_tpu", lambda: True)
    where = SingleDeviceSharding(topo.devices[0])
    with jax.default_matmul_precision("default"):
        for name, fn, build in _window_cell_cases():
            args = jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                               sharding=where),
                jax.eval_shape(build))
            jax.jit(fn).lower(*args).compile()


def _smallthinker_cell_cases():
    """What SmallThinker's cell runs of the same kernels, read from its
    configuration: both forms of the paged kernel on a pool of FOUR kv
    heads (a page ``[16, 4, 128]`` bf16, fewer heads than a packed
    sublane tile) in groups of 7, the window form on a ring of 265 entries
    (a window of 4096, 16-17 cells of 256 a decode row), the full form on
    a table of 1024; and the ReGLU grouped matmul (2560 x 768, 64 experts)
    at a decode step's and a wave's (token, pick) pairs."""
    from paddle_tpu.kernels import grouped_matmul, paged_attention
    from paddle_tpu.models.laguna import _WAVE_ROWS
    with open(os.path.join(REPO, "benchmark", "configs",
                           "smallthinker-21ba3b-d8.json")) as f:
        c = json.load(f)
    eng = c["engine"]
    H, Hk, D = (c["num_attention_heads"], c["num_key_value_heads"],
                c["head_dim"])
    M, bs, chunk = eng["max_slots"], eng["block_size"], eng["prefill_chunk"]
    window = c["sliding_window_size"]
    ring = -(-(window + chunk) // bs) + 1
    E, I, held = (c["hidden_size"], c["moe_ffn_hidden_size"],
                  c["moe_num_primary_experts"])
    bf, i32 = jnp.bfloat16, jnp.int32
    cases = []
    for W, win in ((ring, window), (eng["max_model_len"] // bs, None)):
        for Q in (1, chunk):
            name = (f"paged_attention{'_window' if win else ''}_"
                    f"{'q1' if Q == 1 else 'mq'} {H}/{Hk} heads")

            def fn(q, k, v, tbl, sl, dl, Q=Q, win=win):
                if Q == 1:
                    return paged_attention(q[:, 0], k, v, tbl, sl,
                                           layer=jnp.int32(1), window=win)
                return paged_attention(q, k, v, tbl, sl, draft_lens=dl,
                                       layer=jnp.int32(1), window=win)

            cases.append((name, fn, lambda Q=Q, W=W: (
                jnp.zeros((M, Q, H, D), bf),
                jnp.zeros((2, eng["num_blocks"], Hk, bs, D), bf),
                jnp.zeros((2, eng["num_blocks"], Hk, bs, D), bf),
                jnp.zeros((M, W), i32), jnp.zeros((M,), i32),
                jnp.zeros((M,), i32))))
    for T in (M, _WAVE_ROWS * M):
        rows = T * c["moe_num_active_primary_experts"]
        for K, N, gated in ((E, 2 * I, True), (I, E, False)):
            cases.append((
                f"moe_grouped_matmul {rows} rows {K}x{N}",
                lambda x, w, g, gated=gated: grouped_matmul(
                    x, w, g, layer=jnp.int32(2), gated=gated,
                    activation="relu", use_kernel=True),
                lambda rows=rows, K=K, N=N: (
                    jnp.zeros((rows, K), bf),
                    jnp.zeros((6, held, K, N), bf),
                    jnp.zeros((held,), i32))))
    return cases


SMALLTHINKER_CELL_CASES = ["paged_attention_window_q1 28/4 heads",
                           "paged_attention_window_mq 28/4 heads",
                           "paged_attention_q1 28/4 heads",
                           "paged_attention_mq 28/4 heads",
                           "moe_grouped_matmul 192 rows 2560x1536",
                           "moe_grouped_matmul 192 rows 768x2560",
                           "moe_grouped_matmul 6144 rows 2560x1536",
                           "moe_grouped_matmul 6144 rows 768x2560"]


@pytest.mark.parametrize("case", SMALLTHINKER_CELL_CASES)
def test_four_kv_head_and_reglu_kernels_lower_for_tpu(case, monkeypatch):
    """Both forms of the paged kernel on a four-kv-head pool and the ReGLU
    grouped matmul through the Pallas TPU lowering at the cell's shapes."""
    from paddle_tpu.kernels import dispatch
    monkeypatch.setattr(dispatch, "on_tpu", lambda: True)
    cases = {name: (fn, build)
             for name, fn, build in _smallthinker_cell_cases()}
    assert list(cases) == SMALLTHINKER_CELL_CASES
    fn, build = cases[case]
    exported = jax.export.export(jax.jit(fn), platforms=["tpu"])(
        *jax.eval_shape(build))
    text = exported.mlir_module()
    assert "tpu_custom_call" in text, case
    assert case.split()[0] in text        # the name a device trace shows


@pytest.mark.slow
def test_four_kv_head_and_reglu_kernels_compile_for_v5e_without_a_chip(
        monkeypatch):
    """The same through libtpu's whole compiler for a described v5e."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from paddle_tpu.kernels import dispatch
    try:
        topo = topologies.get_topology_desc(topology_name="v5e:2x2",
                                            platform="tpu")
    except Exception as e:                    # noqa: BLE001 — no libtpu here
        pytest.skip(f"no TPU topology without a chip: {e}")
    monkeypatch.setattr(dispatch, "on_tpu", lambda: True)
    where = SingleDeviceSharding(topo.devices[0])
    with jax.default_matmul_precision("default"):
        for name, fn, build in _smallthinker_cell_cases():
            args = jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                               sharding=where),
                jax.eval_shape(build))
            jax.jit(fn).lower(*args).compile()


def test_preset_is_the_738m_model():
    """The smoke's model is the one with a chip history: 738M parameters
    at LLaMA-7B's shape ratios, kernels and remat on."""
    from paddle_tpu.models.llama import num_params
    cfg, batch, seq = cs.preset()
    assert (cfg.hidden_size, cfg.num_hidden_layers, batch, seq) == \
        (2048, 12, 8, 2048)
    assert cfg.intermediate_size / cfg.hidden_size == 2.6875
    assert round(num_params(cfg) / 1e6) == 738
    assert cfg.use_kernels and cfg.remat
