"""Every Pallas entry point must lower for the TPU from the CPU host.

`jax.export.export(..., platforms=["tpu"])` runs the Pallas -> Mosaic
lowering without a chip, so a block shape the TPU lowering refuses (the
squeezed second-minor kv_heads dim of the old `[pages, page_size, kv_heads,
head_dim]` arena was refused for every kv_heads > 1) fails here, in seconds,
instead of in `engine.warmup()` on the chip.

The lowering does not run libtpu's Mosaic compiler, which has limits of its
own (scoped VMEM, tile shapes).  The slow-marked twin below does: it
compiles the same cases ahead of time for a v5e topology that libtpu
describes without a chip.  Run it before spending chip time on a kernel
change:

    pytest tests/test_pallas_tpu_lowering.py -m slow

The last two tests read a paged engine's own decode and prefill steps for
what a KV write moves besides its rows: the traced program here, the
program compiled for the v5e in the slow twin.
"""

import functools
import math

import jax
import jax.numpy as jnp
import pytest
from jax import export
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from paddle_tpu.distributed import mesh as pmesh
from paddle_tpu.ops import flash_attention as fa
from paddle_tpu.ops import grouped_experts as ge
from paddle_tpu.ops import kda_decode as kd
from paddle_tpu.ops import mla_prefill as mp

BF16 = jnp.bfloat16
MAX_LEN = 1024


@pytest.fixture(autouse=True)
def _dispatch_as_on_tpu(monkeypatch):
    """The dispatchers pick the Pallas path from the backend; take it here
    so the public wrappers (custom_vjp, padding, shard_map) lower too."""
    monkeypatch.setattr(fa, "_on_tpu", lambda: True)
    prev = pmesh.get_mesh()
    yield
    pmesh.set_mesh(prev)


# ---------------------------------------------------------------------------
# cases: (id, fn, [(shape, dtype, partition spec or None), ...], mesh degrees)
# ---------------------------------------------------------------------------


def _sq_loss(out):
    return (out.astype(jnp.float32) ** 2).mean()


def _flash_grad(causal, segments=False, kbias=False):
    def loss(q, k, v, *extra):
        seg = extra[0] if segments else None
        mask = extra[0] if kbias else None
        return _sq_loss(fa.sdpa_array(q, k, v, mask, causal, None, segment_ids=seg))

    return jax.grad(loss, argnums=(0, 1, 2))


def _flash_cases():
    b, s, h, d = 8, 2048, 16, 128  # the bench_llama attention block
    qkv = [((b, s, h, d), BF16, None)] * 3
    yield "flash-causal", _flash_grad(True), qkv, None
    yield "flash-noncausal", _flash_grad(False), qkv, None
    yield ("flash-segments", _flash_grad(True, segments=True),
           qkv + [((b, s), jnp.int32, None)], None)
    yield ("flash-keybias", _flash_grad(False, kbias=True),
           qkv + [((b, 1, 1, s), jnp.float32, None)], None)
    # ragged: a non-128-multiple sequence takes the pad-and-fence path
    yield "flash-ragged", _flash_grad(True), [((4, 200, h, d), BF16, None)] * 3, None
    # under a mesh GSPMD cannot partition a Mosaic call: the dispatcher
    # must shard_map it (hybrid training; tensor-parallel prefill, b=1)
    sharded = [((b, s, 20, d), BF16, P("dp", None, "mp", None))] * 3
    yield "flash-dp2-mp2", _flash_grad(True), sharded, {"dp": 2, "mp": 2}
    yield ("flash-prefill-mp4",
           lambda q, k, v: fa.sdpa_array(q, k, v, None, True, None),
           [((1, 512, h, d), BF16, P(None, None, "mp", None))] * 3, {"mp": 4})
    for sq in (64, 512):
        yield (
            f"decode-q{sq}",
            lambda q, k, v, p: fa.decode_attention_array(q, k, v, p),
            [((1, sq, h, d), BF16, None), ((1, MAX_LEN, h, d), BF16, None),
             ((1, MAX_LEN, h, d), BF16, None), ((), jnp.int32, None)],
            None,
        )


def _paged_fn(quant, max_len=MAX_LEN):
    if quant:
        return lambda q, ak, av, t, p, ks, vs: fa.paged_decode_attention_array(
            q, ak, av, t, p, max_len, kernel="fused", k_scale=ks, v_scale=vs)
    return lambda q, ak, av, t, p: fa.paged_decode_attention_array(
        q, ak, av, t, p, max_len, kernel="fused")


def _paged_args(b, sq, h, hk, ps, quant, cp=1, mp=1, max_len=MAX_LEN):
    d = 128
    n_tab = max_len // ps
    pages = (b * n_tab // cp + 1) * cp
    mp_ax = "mp" if mp > 1 else None
    arena = P("cp" if cp > 1 else None, mp_ax, None, None)
    dt = jnp.int8 if quant else BF16
    args = [
        ((b, sq, h, d), BF16, P(None, None, mp_ax, None)),
        ((pages, hk, ps, d), dt, arena),
        ((pages, hk, ps, d), dt, arena),
        ((b, n_tab), jnp.int32, P()),
        ((b,), jnp.int32, P()),
    ]
    if quant:
        args += [((pages, hk, 1, ps), jnp.float32, arena)] * 2
    return args


def _partials_fn(quant):
    def f(q, ak, av, t, p, *scales):
        base = jnp.arange(t.shape[1], dtype=jnp.int32) * ak.shape[2]
        ks, vs = scales if quant else (None, None)
        return fa._fused_paged_decode_partials_forward(
            q, ak, av, t, base, p, MAX_LEN, 0.1, k_scale=ks, v_scale=vs)

    return f


def _paged_cases():
    for h, hk in ((16, 16), (32, 8)):
        for ps in (8, 128):
            for quant in (False, True):
                tag = f"h{h}-kv{hk}-ps{ps}-{'int8' if quant else 'bf16'}"
                # slot-batched decode, and a chunk prefill (b=1, q rows =
                # the bucket) — the two shapes the engine sends
                yield (f"paged-decode-{tag}", _paged_fn(quant),
                       _paged_args(8, 1, h, hk, ps, quant), None)
                yield (f"paged-chunk-{tag}", _paged_fn(quant),
                       _paged_args(1, 256, h, hk, ps, quant), None)
                yield (f"paged-partials-{tag}", _partials_fn(quant),
                       _paged_args(8, 1, h, hk, ps, quant), None)
    # the serving cell's own walk (`mistral7b_serve.chat32`): 32 slots, 16
    # table entries over a 513-page arena; decode and a verify window of 5
    for sq in (1, 5):
        yield (f"paged-chat32-q{sq}", _paged_fn(False, 2048),
               _paged_args(32, sq, 32, 8, 128, False, max_len=2048), None)
    # `ling3_serve.reason64`'s latent walk: 64 slots, 256 table entries over a
    # 16,385-page arena of ONE 640-wide head that is keys and values
    # (`arena_v=None`), straight to the kernel as `models/ling3.py` calls it
    yield ("paged-reason64",
           lambda q, lat, t, p: fa._fused_paged_decode(q, lat, None, t, p, 32768, 0.07, False),
           [((64, 1, 32, 640), BF16, None), ((16385, 1, 128, 640), BF16, None),
            ((64, 256), jnp.int32, None), ((64,), jnp.int32, None)], None)
    # `kimi_serve.longreason32`'s: 32 slots of 512 table entries (64k tokens) over the same arena
    yield ("paged-longreason32",
           lambda q, lat, t, p: fa._fused_paged_decode(q, lat, None, t, p, 65536, 0.07, False),
           [((32, 1, 32, 640), BF16, None), ((16385, 1, 128, 640), BF16, None),
            ((32, 512), jnp.int32, None), ((32,), jnp.int32, None)], None)
    # `mellum2_serve.mixed32`'s two walks: 32 slots, 8 query rows a KV head over
    # 4 KV heads; a full layer's over the 8,193-page arena, a sliding layer's
    # over the window group's 314 pages from each slot's first visible position
    m2 = [((32, 1, 32, 128), BF16, None), None, None, ((32, 256), jnp.int32, None), ((32,), jnp.int32, None)]
    for tag, pages in (("full", 8193), ("window", 314)):
        arena = ((pages, 4, 128, 128), BF16, None)
        args = [m2[0], arena, arena] + m2[3:]
        if tag == "full":
            yield ("paged-mixed32-full",
                   lambda q, k, v, t, p: fa._fused_paged_decode(q, k, v, t, p, 32768, 0.088, False), args, None)
        else:
            yield ("paged-mixed32-window",
                   lambda q, k, v, t, p, f: fa._fused_paged_decode_window(q, k, v, t, p, f, 32768, 0.088, False),
                   args + [((32,), jnp.int32, None)], None)
    # `lfm2_serve.gen128`'s GQA walk over packed `[k | v]` rows: 128 slots, 4
    # query rows a KV head over 8 KV heads of 128 (2 x 64), 160 table entries
    # (20,480 tokens) over the 11,000-page arena, one operand (`arena_v=None`)
    yield ("paged-gen128-packed",
           lambda q, kv, t, p: fa._fused_paged_decode(q, kv, None, t, p, 20480, 0.125, False),
           [((128, 1, 32, 128), BF16, None), ((11000, 8, 128, 128), BF16, None),
            ((128, 160), jnp.int32, None), ((128,), jnp.int32, None)], None)
    for cp, mp in ((1, 4), (2, 2)):  # the shard_map wrappers
        for quant in (False, True):
            yield (f"paged-cp{cp}-mp{mp}-{'int8' if quant else 'bf16'}",
                   _paged_fn(quant),
                   _paged_args(8, 1, 16, 16, 128, quant, cp=cp, mp=mp),
                   {"cp": cp, "mp": mp})


def _grouped_expert_cases():
    """The expert layer of a decode step at the three serving cells' shapes
    (`ling3_serve.reason64`: 64 tokens, 128 of 512 experts held, 2560 x 768;
    `mellum2_serve.mixed32`: 32 tokens, all 64 experts, 2304 x 896 = 7 x 128;
    `kimi_serve.longreason32`: 32 tokens, 128 of 256 experts held, 2304 x
    1024; `lfm2_serve.gen128`: 128 tokens, the kernel's cap, all 32 experts,
    2048 x 1792 = 14 x 128): two experts in flight are 23.6, 24.8, 28.3 and
    44.0 MB of scoped VMEM."""
    for tag, T, held, D, I in (("reason64", 64, 128, 2560, 768), ("mixed32", 32, 64, 2304, 896),
                               ("longreason32", 32, 128, 2304, 1024), ("gen128", 128, 32, 2048, 1792)):
        def fn(x, weight, counts, w1, w3, w2):
            assert ge.refusal(x, w1) is None
            return ge.grouped_experts(x, weight, *ge.hit_list(counts), w1, w3, w2, False)

        up, down = ((held, D, I), BF16, None), ((held, I, D), BF16, None)
        yield (f"grouped-experts-{tag}", fn,
               [((T, D), BF16, None), ((T, held), jnp.float32, None), ((held,), jnp.int32, None), up, up, down], None)


def _kda_state_cases():
    """The KDA layers' decode recurrence at `ling3_serve.reason64`'s state (64
    slots) and `kimi_serve.longreason32`'s (32): 32 heads of 128 x 128
    float32, a whole slot (2 MiB) a grid step, the state written back in
    place."""
    def fn(q, k, g, v, beta, live, state):
        assert kd.refusal(state) is None
        return kd.kda_state_step(q, k, g, v, beta, live, state, False)

    H, d = 32, 128
    for tag, S in (("reason64", 64), ("longreason32", 32)):
        rows = ((S, H, d), jnp.float32, None)
        yield (f"kda-state-{tag}", fn,
               [rows, rows, rows, rows, ((S, H), jnp.float32, None), ((S,), jnp.bool_, None),
                ((S, H, d, d), jnp.float32, None)], None)


def _mla_prefill_args(s, H, L, selected):
    args = [((s, H, 128), BF16, None), ((s, H, 64), BF16, None), ((L, 640), BF16, None),
            ((512, H * 256), BF16, None), ((), jnp.int32, None), ((), jnp.int32, None)]
    return args + ([((s, L), jnp.bool_, None)] if selected else [])


def _mla_prefill_cases():
    """A prefill chunk's latent attention: `dsv32_serve.longctx16`'s (2048
    rows, 128 heads, 24,576 keys, DeepSeek-V3.2's selected keys) and Ling-3's
    (`ling3_serve.reason64`: 2048 rows, 32 heads, 32,768 keys, causal), K and
    V expanded from 640-wide latent rows a block of 1024 keys at a time."""
    def fn(q_nope, q_pe, lat, w_ukv, n_blocks, start, *chosen):
        mask = chosen[0] if chosen else None
        assert mp.refusal(q_nope, q_pe, lat, w_ukv, 1024, 512, mask) is None
        return mp.mla_prefill(q_nope, q_pe, lat, w_ukv, n_blocks, 1024, 512, 0.135, start, mask)

    yield "mla-prefill-longctx16", fn, _mla_prefill_args(2048, 128, 24576, True), None
    yield "mla-prefill-ling3", fn, _mla_prefill_args(2048, 32, 32768, False), None


CASES = (list(_flash_cases()) + list(_paged_cases()) + list(_grouped_expert_cases())
         + list(_kda_state_cases()) + list(_mla_prefill_cases()))
IDS = [c[0] for c in CASES]


def _avals(args, degrees, devices):
    """ShapeDtypeStructs for a case.  With mesh `degrees` the mesh is
    installed over the first of `devices` and every operand carries its
    NamedSharding — which makes the lowering multi-device, where a Mosaic
    call outside a shard_map is refused."""
    if degrees:
        n = math.prod(degrees.values())
        mesh = pmesh.build_mesh(devices=list(devices)[:n], **degrees)
        sharding_for = lambda spec: NamedSharding(mesh, spec or P())
    else:
        sharding_for = lambda spec: SingleDeviceSharding(devices[0])
    return [
        jax.ShapeDtypeStruct(shape, dt, sharding=sharding_for(spec))
        for shape, dt, spec in args
    ]


@pytest.mark.parametrize("case", CASES, ids=IDS)
def test_lowers_for_tpu(case):
    _, fn, args, degrees = case
    exported = export.export(jax.jit(fn), platforms=["tpu"])(
        *_avals(args, degrees, jax.devices())
    )
    assert "tpu_custom_call" in exported.mlir_module()


def test_mla_prefill_refusal_names_what_it_cannot_take():
    """Shapes the TPU kernel cannot take, each named; the interpreter takes
    any whole tiling."""
    aval = lambda *shape, dt=BF16: jax.ShapeDtypeStruct(shape, dt)
    q, qp, lat, w = aval(2048, 128, 128), aval(2048, 128, 64), aval(24576, 640), aval(512, 128 * 256)
    chosen = aval(2048, 24576, dt=jnp.bool_)
    assert mp.refusal(q, qp, lat, w, 1024, 512, chosen) is None
    assert "rows in tiles of 512" in mp.refusal(aval(2000, 128, 128), aval(2000, 128, 64), lat, w, 1024, 512)
    assert "not whole lanes" in mp.refusal(aval(2048, 128, 64), qp, lat, aval(512, 128 * 192), 1024, 512)
    assert "dtypes" in mp.refusal(q, qp, aval(24576, 640, dt=jnp.float32), w, 1024, 512)
    assert "does not pack" in mp.refusal(q, qp, aval(24576, 640), w, 3072, 512, chosen)
    assert "over the VMEM" in mp.refusal(aval(65536, 128, 128), aval(65536, 128, 64), lat, w, 1024, 512)
    tiny = aval(32, 4, 16), aval(32, 4, 8), aval(64, 128), aval(16, 4 * 32)
    assert "not whole lanes" in mp.refusal(*tiny, 16, 8)
    assert mp.refusal(*tiny, 16, 8, interpret=True) is None


@functools.lru_cache(maxsize=1)
def _v5e_devices():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no libtpu in this installation
        pytest.skip(f"no TPU topology description available: {e}")
    return tuple(topo.devices)


@pytest.mark.slow
@pytest.mark.parametrize("case", CASES, ids=IDS)
def test_compiles_for_v5e(case):
    """Full XLA + Mosaic compile for `TPU v5 lite` without a chip."""
    _, fn, args, degrees = case
    jax.jit(fn).lower(*_avals(args, degrees, _v5e_devices())).compile()


# ---------------------------------------------------------------------------
# a KV write moves its rows and leaves the arena where it lies
# ---------------------------------------------------------------------------


def _paged_engine(config, *, bf16=False, **engine):
    """A paged engine that is traced and compiled here and never run."""
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.inference.engine import ContinuousBatchingEngine
    from paddle_tpu.models.llama import LlamaForCausalLM

    state = paddle.get_rng_state()  # later modules' seedless models stay as they were
    try:
        np.random.seed(1234)
        model = LlamaForCausalLM(config)
        if bf16:
            model = paddle.amp.decorate(model, level="O2", dtype="bfloat16")
        return ContinuousBatchingEngine(model, **engine)
    finally:
        paddle.set_rng_state(state)


def _arena_shaped_equations(jaxpr, shape, found):
    for eqn in jaxpr.eqns:
        subs = [
            getattr(sub, "jaxpr", sub)
            for param in eqn.params.values()
            for sub in (param if isinstance(param, (list, tuple)) else [param])
        ]
        subs = [sub for sub in subs if hasattr(sub, "eqns")]
        for sub in subs:
            _arena_shaped_equations(sub, shape, found)
        # a call's results are its body's, which the walk has seen
        if not subs and any(getattr(v.aval, "shape", None) == shape for v in eqn.outvars):
            found.append(eqn)
    return found


@pytest.mark.parametrize("step", ["decode", "prefill"])
def test_engine_step_jaxpr_holds_the_store_alone(step):
    """In the traced `to_static` step nothing arena-shaped is made but by the
    store's own scatter.  Under `dispatch.apply`'s `jax.vjp` a scatter whose
    rows carry a tangent is rewritten by its JVP rule into a scatter of ids
    over a u32 twin of the arena, compares and selects over the whole of it:
    on every platform, every step."""
    from conftest import paged_engine_steps

    from paddle_tpu.models.llama import LlamaConfig

    eng = _paged_engine(LlamaConfig.tiny(), slots=3, max_len=64,
                        prefill_buckets=[16], page_size=8)
    shape = tuple(eng._arenas[0].k.shape)
    fn, args = paged_engine_steps(eng, 16)[step]
    entry, *arrays = fn._prepare(args, {})
    found = _arena_shaped_equations(jax.make_jaxpr(entry.jitted)(*arrays).jaxpr, shape, [])
    names = [e.primitive.name for e in found]
    assert names.count("scatter") == 2 * len(eng._arenas)  # K and V of each layer
    # `stop_gradient` is the identity; the fill is the zero tangent `jax.vjp`
    # makes for the arena it returns, a constant that nothing reads
    fills = [e for e in found if e.primitive.name == "broadcast_in_dim"]
    assert all(v.aval.shape == () for e in fills for v in e.invars)
    assert set(names) <= {"scatter", "stop_gradient", "broadcast_in_dim"}, names


@pytest.mark.slow
@pytest.mark.parametrize("step", ["decode", "prefill"])
def test_engine_step_compiles_for_v5e_without_arena_copies(step):
    """The same two steps compiled for `TPU v5 lite` at the serving cell's
    arena, bf16[513,8,128,128]: a scatter that leaves `kv_heads` as a window
    dim between indexed dims runs in another layout, and XLA:TPU copies the
    arena there and back around it (134 MB each way, per arena, per step)."""
    from conftest import hlo_results, paged_engine_steps

    from paddle_tpu.models.llama import LlamaConfig

    config = LlamaConfig.tiny(
        hidden_size=4096, intermediate_size=512, num_attention_heads=32,
        num_key_value_heads=8, num_hidden_layers=1, max_position_embeddings=2048)
    eng = _paged_engine(config, bf16=True, slots=32, max_len=2048,
                        prefill_buckets=[256], page_size=128)
    shape = tuple(eng._arenas[0].k.shape)
    assert shape == (513, 8, 128, 128)
    fn, args = paged_engine_steps(eng, 256)[step]
    entry, *arrays = fn._prepare(args, {})
    on_chip = SingleDeviceSharding(_v5e_devices()[0])
    avals = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=on_chip), arrays)
    compiled = entry.jitted.lower(*avals).compile()
    made = hlo_results(compiled.as_text(), shape)
    assert any(op == "scatter" for _, op in made)
    bad = [(n, op) for n, op in made
           if op in ("copy", "select") or (op == "fusion" and ("copy" in n or "select" in n))]
    assert bad == []
    # one arena is 134.5 MB: a program that copies one needs that much room
    assert compiled.memory_analysis().temp_size_in_bytes < 64 * 2**20


def _lfm2_engine(**over):
    """An LFM2 engine of one attention and one convolution layer, traced and
    compiled here and never run; its attention arena holds packed `[k | v]`
    rows, one operand."""
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.inference.engine import ContinuousBatchingEngine
    from paddle_tpu.models import Lfm2MoeConfig, Lfm2MoeForCausalLM

    engine = over.pop("engine")
    state = paddle.get_rng_state()
    try:
        np.random.seed(1234)
        model = Lfm2MoeForCausalLM(Lfm2MoeConfig.tiny(
            num_hidden_layers=2, layer_types=["full_attention", "conv"], **over))
        return ContinuousBatchingEngine(model, **engine)
    finally:
        paddle.set_rng_state(state)


@pytest.mark.parametrize("step", ["decode", "prefill"])
def test_lfm2_step_jaxpr_holds_the_packed_store_alone(step):
    """The packed arena of `lfm2_serve.gen128` is made in the traced step by
    its one store alone: K and V go in as one row, the walk (or the chunk
    loop) reads the arena as it lies."""
    from conftest import paged_engine_steps

    eng = _lfm2_engine(num_dense_layers=0, engine=dict(slots=3, max_len=64, prefill_buckets=[16], page_size=8))
    shape = tuple(eng._arenas[0].kv.shape)
    assert shape[-1] == 2 * eng.model.config.head_dim
    fn, args = paged_engine_steps(eng, 16)[step]
    entry, *arrays = fn._prepare(args, {})
    found = _arena_shaped_equations(jax.make_jaxpr(entry.jitted)(*arrays).jaxpr, shape, [])
    names = [e.primitive.name for e in found]
    assert names.count("scatter") == 1  # one layer with rows, one packed row kind
    assert set(names) <= {"scatter", "stop_gradient", "broadcast_in_dim"}, names


@pytest.mark.slow
@pytest.mark.parametrize("step", ["decode", "prefill"])
def test_lfm2_step_compiles_for_v5e_without_arena_copies(step):
    """The same two steps compiled for `TPU v5 lite` at the cell's widths
    (hidden 2048, 8 KV heads of a packed 128, 128 slots, page 128): nothing
    arena-shaped is copied or selected around the store or the walk.  Dense
    feed-forward layers and a cut vocabulary keep the build small; they do
    not touch the arena."""
    from conftest import hlo_results, paged_engine_steps

    eng = _lfm2_engine(
        vocab_size=4096, hidden_size=2048, intermediate_size=7168, num_attention_heads=32,
        num_key_value_heads=8, num_dense_layers=2, max_position_embeddings=20480, dtype="bfloat16",
        engine=dict(slots=128, max_len=20480, prefill_buckets=[512], pool_pages=513))
    shape = tuple(eng._arenas[0].kv.shape)
    assert shape == (513, 8, 128, 128)
    fn, args = paged_engine_steps(eng, 512)[step]
    entry, *arrays = fn._prepare(args, {})
    on_chip = SingleDeviceSharding(_v5e_devices()[0])
    avals = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=on_chip), arrays)
    compiled = entry.jitted.lower(*avals).compile()
    made = hlo_results(compiled.as_text(), shape)
    bad = [(n, op) for n, op in made
           if op in ("copy", "select") or (op == "fusion" and ("copy" in n or "select" in n))]
    assert bad == []
    assert compiled.memory_analysis().temp_size_in_bytes < 64 * 2**20  # the arena is 134.5 MB
