"""The Pallas kernels of the main paths, put before the TPU compiler.

Interpret mode (every other kernel test here) cannot see what Mosaic
refuses: a block whose row count is not a multiple of the sublane tile,
a slice off the tiling, too much VMEM. The TPU compiler is installed in
the sandbox and compiles for a chip that is *described*, not attached
(``jax.experimental.topologies``), so these cases lower and compile each
kernel at the real bench shapes for one v5e chip. Nothing runs: a pass
says the chip's compiler takes the kernel, not that its numbers are
right (the interpret-mode tests and ``chip_smoke.py`` say that).

Kernels, and the dense serving engine's two programs (whose cost is in
what the compiler makes of the KV-cache update, not in a kernel's
arithmetic); skipped where the topology cannot be described, and kept
to about a minute in total.
"""

import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs to /tmp

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from horovod_tpu.ops.pallas import conv_bn_act, fused_optimizer
from horovod_tpu.ops.pallas._backend import shard_over_batch
from horovod_tpu.ops.pallas.flash_attention import flash_attention
from horovod_tpu.runtime.fusion_buffer import bucket_elems
from horovod_tpu.utils.env import DEFAULT_FUSION_BUCKET_QUANTUM_BYTES

BF16, F32 = jnp.bfloat16, jnp.float32


@pytest.fixture(scope="module")
def v5e():
    """The four devices of a described v5e 2x2 host, with the kernels
    forced out of interpret mode and the persistent compile cache off (an
    entry written for a described chip cannot be read back without one,
    and warns on every later compile)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as exc:  # no libtpu / no such topology in this build
        pytest.skip(f"cannot describe a v5e topology here: {exc}")
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    patch = pytest.MonkeyPatch()
    patch.setenv("HOROVOD_PALLAS_INTERPRET", "0")
    patch.setenv("HOROVOD_FUSED_BN_ACT", "1")
    yield list(topo.devices)
    patch.undo()
    jax.config.update("jax_enable_compilation_cache", cache_was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def chip(v5e):
    return SingleDeviceSharding(v5e[0])


def _kernels_in(fn, chip, *specs):
    """Compile ``fn`` for the described chip at ``(shape, dtype)`` specs;
    the number of Mosaic kernels in the compiled program."""
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=chip)
            for shape, dtype in specs]
    text = jax.jit(fn).lower(*args).compile().as_text()
    return text.count("tpu_custom_call")


def _calls(kernel, text):
    """How often the compiled program calls the Mosaic kernel ``kernel``."""
    return len(re.findall(rf"%{kernel}[.\d]* = [^\n]*? custom-call\(", text))


@pytest.mark.parametrize("shape,causal,traced", [
    ((16, 12, 1024, 64), True, False),   # GPT-2-small bench: batch 16, seq 1024
    ((8, 16, 512, 64), False, False),    # BERT-Large bench: batch 8, seq 512
    # the ring's call: traced offsets, the causal kernels' ladder of rungs
    ((2, 12, 1024, 64), True, True),
], ids=["gpt2", "bert-large", "gpt2-traced-offsets"])
def test_flash_attention_forward_and_backward(chip, shape, causal, traced):
    def loss(q, k, v, at=0):
        return flash_attention(q, k, v, causal=causal, q_offset=at,
                               k_offset=at).astype(F32).sum()

    spec = (shape, BF16)
    # forward (residual-saving) + the dq and dk/dv backward kernels
    assert _kernels_in(jax.value_and_grad(loss, argnums=(0, 1, 2)), chip,
                       spec, spec, spec, *[((), jnp.int32)] * traced) >= 3


def _inception_bn_act_shapes():
    """Every activation shape Inception-V3 hands ``scale_bias_act`` at
    the bench's batch 32 / 299x299, by walking the model abstractly."""
    from horovod_tpu.models import InceptionV3

    seen = []
    patch = pytest.MonkeyPatch()
    patch.setattr(conv_bn_act, "scale_bias_act",
                  lambda x, s, b: (seen.append((x.shape, x.dtype)),
                                   conv_bn_act._sba_jnp(x, s, b))[1])
    try:
        model = InceptionV3(num_classes=1000, dtype=BF16)
        x = jax.ShapeDtypeStruct((32, 299, 299, 3), F32)
        variables = jax.eval_shape(
            lambda x: model.init(jax.random.PRNGKey(0), x, train=False), x)
        seen.clear()
        jax.eval_shape(
            lambda v, x: model.apply(v, x, train=True,
                                     mutable=["batch_stats"]),
            variables, x)
    finally:
        patch.undo()
    return sorted(set(seen), key=str)


def test_scale_bias_act_at_every_inception_shape(chip):
    """One program holding the fused BN+ReLU epilogue, forward and
    backward, at each Inception-V3 activation shape. The stem's
    (32,149,149,32) used to be refused: its 177,608 lane rows have no
    divisor under 512 that is a multiple of 8."""
    shapes = _inception_bn_act_shapes()
    assert ((32, 149, 149, 32), BF16) in shapes

    def loss(xs, ss, bs):
        return sum(conv_bn_act.scale_bias_act(x, s, b).astype(F32).sum()
                   for x, s, b in zip(xs, ss, bs))

    def both(xs, ss, bs):
        return jax.value_and_grad(loss, argnums=(0, 1, 2))(xs, ss, bs)

    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                    sharding=chip)
    xs = [sds(shape, dtype) for shape, dtype in shapes]
    cs = [sds(shape[-1:], F32) for shape, _ in shapes]
    text = jax.jit(both).lower(xs, cs, cs).compile().as_text()
    # the kernel serves channel counts that pack 128 lanes exactly; the
    # other shapes are the jnp chain by design (conv_bn_act docstring)
    packs = [s for s, _ in shapes
             if s[-1] % 128 == 0 or 128 % s[-1] == 0]
    assert text.count("tpu_custom_call") == len(packs) >= 8


# f32 parameter counts, from jax.eval_shape of model.init: GPT-2-small at
# the padded vocab 50304 / 1024 positions, BERT-Large at 30522 / 512.
_PARAMS = {"gpt2": 124_475_904, "bert-large": 334_090_240}


def _zero_shard_elems(n_params, world,
                      quantum=DEFAULT_FUSION_BUCKET_QUANTUM_BYTES):
    """Per-chip flat f32 shard length, as ``zero.build_spec`` lays one
    float32 group out over ``world`` chips."""
    return bucket_elems(-(-n_params // world), 4, quantum)


@pytest.mark.parametrize("n", [
    pytest.param(_zero_shard_elems(_PARAMS["gpt2"], 1), id="gpt2-1chip"),
    pytest.param(_zero_shard_elems(_PARAMS["gpt2"], 4), id="gpt2-4chips"),
    pytest.param(_zero_shard_elems(_PARAMS["bert-large"], 4),
                 id="bert-large-4chips"),
    # HOROVOD_FUSION_BUCKET_QUANTUM=0 leaves shards unpadded
    pytest.param(_zero_shard_elems(_PARAMS["bert-large"], 4, quantum=0),
                 id="bert-large-4chips-unpadded"),
    # a quarter of a BERT-Large flat f32 table (334M parameters):
    # 652,344 lane rows, whose largest divisor under 512 is 462 — not a
    # multiple of 8, so Mosaic refused the divisor-search block
    pytest.param(83_500_032, id="bench-table-quarter"),
])
def test_flat_adamw_shard_kernel(chip, n):
    assert n % 128 == 0

    def update(master, mu, nu, grad, scalars):
        return fused_optimizer.pallas_flat_adamw(
            master, mu, nu, grad, scalars, eps=1e-8, out_dtype=BF16)

    buf = ((n,), F32)
    assert _kernels_in(update, chip, buf, buf, buf, ((n,), BF16),
                       ((6,), F32)) == 1


def _feed_is_aliased(text, params, cache):
    """The compiled module's header aliases the token feed's parameter
    (the first after the parameters' and the cache's leaves; earlier
    where ``jit`` dropped parameters that nothing reads, as the head's
    from a piece of a prompt that has none) to the result after the
    cache's leaves."""
    n_params = len(jax.tree.leaves(params))
    n_cache = len(jax.tree.leaves(cache))
    header = text.split("\n", 1)[0]
    assert "input_output_alias" in header
    found = re.search(rf"\{{{n_cache}\}}: \((\d+), \{{\}}, may-alias\)", header)
    return bool(found) and int(found[1]) <= n_params + n_cache and bool(
        re.search(rf"= s32\[\d+\]\S* parameter\({found[1]}\)", text))


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_dense_engine_updates_its_cache_in_place(chip, monkeypatch, program):
    """GPT-2-small's widths at the serving cell's size (128 slots x 1024
    positions; two layers keep it to seconds): the program's result
    aliases the whole donated cache and the token feed, and nothing in
    it copies a cache leaf or loops over one (XLA:TPU turns a batched
    scatter into one serial trip per row; an undonated cache is copied
    whole, per leaf). In the decode step nothing but the kernels touches
    a leaf at all, and a layer has one: the attention reads the live
    lane tiles of each row (ops/pallas/decode_attention), not the whole
    of it, and writes the new key and value columns into the last of
    them itself."""
    from horovod_tpu.models.transformer import Transformer
    from horovod_tpu.serve.kv_cache import DecodeEngine

    def on_chip(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=chip), tree)

    layers, slots = 2, 128
    model = Transformer(vocab_size=50304, d_model=768, num_layers=layers,
                        num_heads=12, d_ff=3072, max_seq=1024, causal=True,
                        dtype=BF16)
    params = on_chip(jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32),
                           train=False)["params"]))
    # nothing can be allocated on a described chip: the engine holds the
    # cache as shapes, which is all that lowering asks of it
    monkeypatch.setattr(DecodeEngine, "_allocate_cache",
                        lambda self: on_chip(self._cache_shapes()))
    eng = DecodeEngine(model, params, num_slots=slots)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=chip)

    if program == "decode":
        lowered = eng._decode_fn.lower(params, eng._cache, i32(slots),
                                       i32(slots))
    else:
        lowered = eng._prefill_fn(256).lower(params, eng._cache, i32(slots),
                                             i32(1, 256), i32(), i32())
    compiled = lowered.compile()
    text, memory = compiled.as_text(), compiled.memory_analysis()

    leaf = r"bf16\[128,12,64,1024\]"
    assert re.search(leaf + r"\S* parameter\(", text)   # the text names them so
    loops = [line for line in text.splitlines()
             if re.search(r" while\(", line) and re.search(leaf, line)]
    copies = re.findall(rf"= {leaf}\S* copy\(", text)
    assert not loops and not copies, (loops, copies)
    param_bytes = sum(x.size * x.dtype.itemsize
                      for x in jax.tree.leaves(params))
    feed_bytes = 4 * slots
    assert memory.alias_size_in_bytes == eng.cache_bytes() + feed_bytes
    assert _feed_is_aliased(text, params, eng._cache)
    # parameters (their small vectors tile-padded: a percent's slack) and
    # one cache, whose leaves no layout pads
    assert memory.argument_size_in_bytes <= (1.01 * param_bytes
                                             + eng.cache_bytes())
    # a layer of the decode step is one kernel, which fetches the rows'
    # live lane tiles and writes the new columns of both leaves in place;
    # the prefill writes one row's slice, attends over the whole of its
    # fresh single-row cache and needs no kernel
    assert text.count("tpu_custom_call") == (
        layers if program == "decode" else 0)
    if program == "decode":
        assert len(re.findall(r"%decode_attention[.\d]* = .* custom-call\(",
                              text)) == layers
        # nothing but the kernels (and the result tuple) takes a whole
        # leaf: no fusion reads all 1,024 positions of every slot
        leaves = set(re.findall(rf"(%[\w.\-]+) = {leaf}", text))
        readers = [
            line for line in text.splitlines()
            if " = " in line and "custom-call(" not in line
            and " tuple(" not in line
            and leaves & set(re.findall(r"%[\w.\-]+",
                                        line.split(" = ", 1)[1]))]
        assert len(leaves) == 4 * layers and not readers, readers


@pytest.mark.parametrize("program", ["decode", "prefill_32768"])
def test_hybrid_engine_fits_and_updates_its_cache_in_place(chip, monkeypatch,
                                                           program):
    """MiniCPM-SALA as the benchmark runs it (benchmark/configs/
    minicpm-sala.json: four layers at full width, 16 slots x 32768
    positions, bfloat16 weights): the 16-row decode step and the prefill
    of the largest bucket compile for one v5e chip, arguments plus
    temporaries stay under its 16 GB, the result aliases every leaf of
    the donated cache - keys/values, compressed keys and the float32
    state alike - and the token feed, and nothing copies a cache leaf."""
    import json

    from benchmark.runners.serve_sala import build_model
    from horovod_tpu.serve.kv_cache import DecodeEngine

    def on_chip(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=chip), tree)

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "minicpm-sala.json")) as f:
        cfg = json.load(f)["as_run"]
    slots, seq = 16, cfg["max_seq"]
    model = build_model(cfg)
    params = on_chip(jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
                           )["params"]))
    monkeypatch.setattr(DecodeEngine, "_allocate_cache",
                        lambda self: on_chip(self._cache_shapes()))
    eng = DecodeEngine(model, params, num_slots=slots)
    assert eng.cache_bytes_by_kind() == {
        "kv": 2 * slots * 2 * 128 * seq * 2,              # 537 MB
        "compressed": slots * 2 * 128 * 2048 * 2,         # 16.8 MB
        "state": 3 * slots * 32 * 128 * 128 * 4}          # 101 MB

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=chip)

    if program == "decode":
        lowered = eng._decode_fn.lower(params, eng._cache, i32(slots),
                                       i32(slots))
    else:
        lowered = eng._prefill_fn(seq).lower(params, eng._cache, i32(slots),
                                             i32(1, seq), i32(), i32())
    compiled = lowered.compile()
    text, memory = compiled.as_text(), compiled.memory_analysis()
    assert 0 < memory.alias_size_in_bytes - eng.cache_bytes() <= 4096
    assert _feed_is_aliased(text, params, eng._cache)
    assert (memory.argument_size_in_bytes + memory.temp_size_in_bytes
            < 16e9)
    leaves = (rf"bf16\[{slots},2,128,{seq}\]", rf"bf16\[{slots},2,128,2048\]",
              rf"f32\[{slots},32,128,128\]")
    for leaf in leaves:
        assert re.search(leaf + r"\S* parameter\(", text), leaf
        assert not re.findall(rf"= {leaf}\S* copy\(", text), leaf
    # the decode step writes one key, one value and one compressed key a
    # row through the in-place kernel; the prefill writes slices, and its
    # one sparse layer attends through the prompt kernel: no loop of the
    # program carries a block of float32 scores against every key
    assert text.count("tpu_custom_call") == (3 if program == "decode" else 1)
    if program != "decode":
        assert len(re.findall(
            r"%sparse_prompt_attention[.\d]* = [^\n]*? custom-call\(",
            text)) == 1
        assert not re.search(rf"f32\[[\d,]*128,{seq}\]", text)
        assert eng.stats()["prefill_sparse_kernel"] is True


@pytest.mark.parametrize("seq,heads,kv_heads,head_dim,block_size,dtype", [
    (16384, 32, 2, 128, 64, BF16),    # sala-serve-long-c1's two buckets
    (32768, 32, 2, 128, 64, BF16),
    (1, 1, 1, 8, 8, BF16),            # the smallest shape takes_kernel admits
    (301, 4, 2, 32, 16, F32)],        # the toy model's, no whole tile
    ids=["sala_16384", "sala_32768", "smallest", "toy_float32"])
def test_sparse_prompt_attention(chip, seq, heads, kv_heads, head_dim,
                                 block_size, dtype):
    """The block-sparse prompt kernel at the corners of its envelope
    (stated beside ``sparse_attention.takes_kernel``): Mosaic takes the
    int8 table's conversion, the roll by a traced number of lanes and the
    data-dependent block indices, in 2 MB of float32 scores a step."""
    from horovod_tpu.ops.pallas import sparse_attention

    assert sparse_attention.takes_kernel(heads, kv_heads, head_dim,
                                         block_size)
    kernels = _kernels_in(
        lambda q, k, v, bits: sparse_attention.sparse_prompt_attention(
            q, k, v, bits, block_size=block_size),
        chip, ((1, seq, heads, head_dim), dtype),
        ((1, seq, kv_heads, head_dim), dtype),
        ((1, seq, kv_heads, head_dim), dtype),
        ((1, kv_heads, seq, -(-seq // block_size)), jnp.int8))
    assert kernels == 1


@pytest.mark.parametrize("tokens,d,room", [
    (4096, 4096, 40960),     # granite-serve-chat-c1's largest bucket, x 10
    (4096, 6144, 4096)],     # kexaone-serve-mixed-c1's turn of a prompt
    ids=["granite", "kexaone"])
def test_expert_combine(chip, tokens, d, room):
    """The way back from a grouped product at the two held-share cells'
    shapes, accumulating as ``experts_grouped_held``'s loop calls it:
    Mosaic takes the strided loads and stores at sublanes read from SMEM,
    the loop of a traced number of rows and the block indices that
    depend on the prefetched ``live``, with 1,024 columns of every
    token's sum twice over in VMEM beside ``acc``'s (64 MB), as one
    custom call that writes its sum where ``acc`` lay."""
    from horovod_tpu.ops.pallas.expert_combine import expert_combine

    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=chip)
            for shape, dtype in (((room, d), F32), ((room,), jnp.int32),
                                 ((room,), F32), ((), jnp.int32),
                                 ((tokens, d), F32))]
    text = jax.jit(
        lambda y, token, weight, live, acc: expert_combine(
            y, token, weight, live, tokens, acc),
        donate_argnums=4).lower(*args).compile().as_text()
    assert text.count("tpu_custom_call") == 1
    assert len(re.findall(
        r"%expert_combine[.\d]* = [^\n]*? custom-call\(", text)) == 1
    # no copy of the sum: the kernel adds where ``acc`` lies
    assert not re.search(rf"= f32\[{tokens},{d}\][^\n]*? copy\(", text)


@pytest.mark.parametrize("form,d,d_ff,experts,count,top_k,tokens", [
    # k-exaone-236b-a23b: a sixteenth held, the first turn and the loop's
    # (granite's one turn is read in its engine's test above)
    ("held-in-turns", 6144, 2048, 128, 8, 8, 1024),
    # sdar-30b-a3b: every expert held
    ("all", 2048, 768, 128, None, 8, 512)])
def test_ten_expert_layers_hold_two_grouped_product_traces(
        chip, form, d, d_ff, experts, count, top_k, tokens):
    """A prompt's expert products in both grouped forms at two cells'
    widths, ten layers of them in one program: Mosaic takes both kernels of
    ``ops/pallas/grouped_product`` (gate and up fused; down in float32),
    the compiled text calls them twice a layer's turn and holds no
    ``ragged-dot``. And what a start pays for: the traced program holds
    TWO distinct jitted callables round a ``grouped_product`` kernel and
    one round the visits, however many layers and turns call them - each
    is traced once a process and lowered once a module. A later edit
    that splits a trace (an argument that differs between layers, or
    between the first turn and the loop's body) fails here before it
    costs a cell its ``setup_s`` (PERF.md section 6, PR 49)."""
    from horovod_tpu.models.hybrid import RoutedExperts
    from horovod_tpu.ops.pallas._backend import kernels_in

    layers = 10
    layer = RoutedExperts(num_experts=experts, top_k=top_k, d_ff=d_ff,
                          shared=0, count=count, dtype=BF16)
    x = jax.ShapeDtypeStruct((1, tokens, d), BF16, sharding=chip)
    # one layer's experts under all ten: the traces are what is counted
    params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, BF16, sharding=chip),
        jax.eval_shape(lambda: layer.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8, d), BF16))["params"]))

    def program(params, x):
        for _ in range(layers):
            x = x + layer.apply({"params": params}, x)
        return x

    traced = jax.jit(program).trace(params, x)
    calls = kernels_in(traced.jaxpr).count("grouped_product")
    turns = 2 if form == "held-in-turns" else 1
    assert calls == 2 * turns * layers

    def holders(jaxpr, found):
        # the jitted functions whose own equations hold a grouped-product
        # kernel, and the kernels' own jaxprs: what is traced and lowered
        for eqn in jaxpr.eqns:
            for value in eqn.params.values():
                for sub in value if isinstance(value, (tuple, list)) \
                        else (value,):
                    inner = getattr(sub, "jaxpr", sub)
                    if not hasattr(inner, "eqns"):
                        continue
                    kernels = [e for e in inner.eqns
                               if e.primitive.name == "pallas_call"
                               and e.params["name"] == "grouped_product"]
                    if eqn.primitive.name in ("pjit", "jit") and kernels:
                        found[id(inner)] = {id(e.params["jaxpr"])
                                            for e in kernels}
                    holders(inner, found)
        return found

    held = holders(traced.jaxpr.jaxpr, {})
    assert len(held) == 1                      # one callable a layer
    assert [len(k) for k in held.values()] == [2]   # the two kernels

    text = traced.lower().compile().as_text()
    assert _calls("grouped_product", text) == 2 * turns * layers
    assert _calls("expert_combine", text) == turns * layers
    assert not re.findall(r"%ragged-dot", text)


@pytest.mark.parametrize("program", ["decode", "prefill_chunk",
                                     "prefill_last"])
def test_retention_engine_fits_and_rewrites_its_cache_in_place(
        chip, monkeypatch, capsys, program):
    """Brumby-14B-Base as the benchmark runs it (benchmark/configs/
    brumby-14b.json: four power-retention layers at full width, 32 slots,
    bfloat16 weights): the 32-row decode step and the two programs of
    a prompt run in 1,024-token pieces (every piece but the last, and
    the last with the head) compile for one v5e chip - Mosaic takes both
    kernels of ops/pallas/power_retention at these shapes - arguments
    plus temporaries stay under its 16 GB (printed: run with ``-s``),
    the result aliases every leaf of the donated cache - which holds
    states and normalisers and no leaf with a position axis - and the
    token feed, and nothing copies a state or a normaliser: the decode
    step's kernel rewrites all 4.4 GB of it where it lies, and a piece
    reads its slot's row of each leaf and writes it back in place."""
    import json

    from benchmark.runners.serve_brumby import build_model
    from horovod_tpu.serve.kv_cache import PREFILL_CHUNK, DecodeEngine

    def on_chip(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=chip), tree)

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "brumby-14b.json")) as f:
        cfg = json.load(f)["as_run"]
    layers, slots, turns = 4, 32, 65
    model = build_model(cfg)
    params = on_chip(jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
                           )["params"]))
    monkeypatch.setattr(DecodeEngine, "_allocate_cache",
                        lambda self: on_chip(self._cache_shapes()))
    eng = DecodeEngine(model, params, num_slots=slots)
    # 8,256 rows of 128 values and a normaliser of 8,256 a key/value
    # head, padded to 65 whole rows of distances: 4.40 GB
    assert eng.cache_bytes_by_kind() == {
        "kv": 0, "compressed": 0,
        "state": layers * slots * 8 * turns * 128 * (128 + 1) * 4}
    assert "decode_attention" not in eng.decode_kernels

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=chip)

    if program == "decode":
        lowered = eng._decode_fn.lower(params, eng._cache, i32(slots),
                                       i32(slots))
    else:
        # (tokens, offset, slot), and the true length before the slot
        # for the last piece
        scalars = (i32(),) * (2 if program == "prefill_chunk" else 3)
        lowered = eng._piece_fn(program).lower(
            params, eng._cache, i32(slots), i32(1, PREFILL_CHUNK), *scalars)
    compiled = lowered.compile()
    text, memory = compiled.as_text(), compiled.memory_analysis()
    with capsys.disabled():
        print(f"\nbrumby {program}: arguments "
              f"{memory.argument_size_in_bytes / 1e9:.3f} GB, temporaries "
              f"{memory.temp_size_in_bytes / 1e9:.3f} GB, aliased "
              f"{memory.alias_size_in_bytes / 1e9:.3f} GB")
    assert 0 < memory.alias_size_in_bytes - eng.cache_bytes() <= 4096
    assert _feed_is_aliased(text, params, eng._cache)
    assert (memory.argument_size_in_bytes + memory.temp_size_in_bytes
            < 16e9)
    state, norm = (rf"f32\[{slots},8,{turns},128,128\]",
                   rf"f32\[{slots},8,{turns},128\]")
    for leaf in (state, norm):
        assert len(re.findall(rf"= {leaf}\S* parameter\(\d+\), sharding",
                              text)) == layers, leaf
    # the refusal is for the state. A normaliser is 0.8% of it (8.5 MB a
    # layer): it is aliased like the state (the sizes above), and the
    # compiler may stage it through VMEM on its way into the kernel
    assert not re.findall(rf"= {state}\S* copy(-start)?\(", text)
    # a layer is one kernel: the step's pass over the state, or a
    # piece's read of the state between chunks (inside its scan). A
    # piece without the head returns the states alone, so nothing reads
    # what its last layer's mixer puts out: the compiler drops that
    # layer's read of the state with its MLP, and the head's and those
    # layers' weights are no argument of the program
    kernel = "retention_step" if program == "decode" else "retention_read"
    kernels = layers - 1 if program == "prefill_chunk" else layers
    assert text.count("tpu_custom_call") == kernels
    assert len(re.findall(rf"%{kernel}[.\d]* = [^\n]*? custom-call\(",
                          text)) == kernels
    param_bytes = sum(x.size * x.dtype.itemsize
                      for x in jax.tree.leaves(params))
    assert (memory.argument_size_in_bytes - eng.cache_bytes()
            < (0.63 if program == "prefill_chunk" else 1.01) * param_bytes)


@pytest.mark.parametrize("program", ["decode", "prefill_8192"])
def test_latent_experts_engine_fits_and_updates_its_cache_in_place(
        chip, monkeypatch, capsys, program):
    """Xing4.0-29B-A4B as the benchmark runs it (benchmark/configs/
    xing4-29b-a4b.json: one dense and five expert layers at full width,
    all 64 experts, four residual streams, 64 slots of 8,192 positions,
    bfloat16 weights): the 64-row decode step and the prefill of the
    largest bucket compile for one v5e chip, arguments plus temporaries
    stay under its 16 GB (printed: run with ``-s``), the result aliases
    every leaf of the donated cache - one 512-wide latent and one
    64-wide rotary key a position a layer, nothing a head, and the
    expert layers' counters - and the token feed, and nothing copies a
    latent leaf. The decode step writes its columns through
    ``kv_cache_write`` (two leaves a layer) and attends through
    ``ops/pallas/latent_attention`` (Mosaic takes it at 32 heads, a
    512-wide latent and tiles of 1,024 positions); the prefill attends
    through the flash kernel at a padded width of 256
    and multiplies its sorted pairs through ``ops/pallas/grouped_product``,
    gate and up in one kernel and down in another."""
    import json

    from benchmark.runners.serve_xing import build_model
    from horovod_tpu.serve.kv_cache import DecodeEngine

    def on_chip(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=chip), tree)

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "xing4-29b-a4b.json")) as f:
        cfg = json.load(f)["as_run"]
    layers, slots, bucket, seq = 6, 64, 8192, 8192
    model = build_model(cfg)
    params = on_chip(jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
                           )["params"]))
    monkeypatch.setattr(DecodeEngine, "_allocate_cache",
                        lambda self: on_chip(self._cache_shapes()))
    eng = DecodeEngine(model, params, num_slots=slots)
    assert eng.cache_bytes_by_kind() == {
        "kv": 0, "compressed": 0, "state": 0,
        "latent": layers * slots * seq * (512 + 64) * 2,     # 3.62 GB
        "counter": (layers - 1) * 3 * 64 * 4}
    assert eng._counts
    assert "latent_decode_attention" in eng.decode_kernels
    assert "decode_attention" not in eng.decode_kernels

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=chip)

    if program == "decode":
        lowered = eng._decode_fn.lower(params, eng._cache, i32(slots),
                                       i32(slots))
    else:
        lowered = eng._prefill_fn(bucket).lower(
            params, eng._cache, i32(slots), i32(1, bucket), i32(), i32())
    compiled = lowered.compile()
    text, memory = compiled.as_text(), compiled.memory_analysis()
    with capsys.disabled():
        print(f"\nxing {program}: arguments "
              f"{memory.argument_size_in_bytes / 1e9:.3f} GB, temporaries "
              f"{memory.temp_size_in_bytes / 1e9:.3f} GB, aliased "
              f"{memory.alias_size_in_bytes / 1e9:.3f} GB")
    assert 0 < memory.alias_size_in_bytes - eng.cache_bytes() <= 8192
    assert _feed_is_aliased(text, params, eng._cache)
    assert (memory.argument_size_in_bytes + memory.temp_size_in_bytes
            < 16e9)
    latent, rope_key = (rf"bf16\[{slots},512,{seq}\]",
                        rf"bf16\[{slots},64,{seq}\]")
    for leaf in (latent, rope_key):
        assert len(re.findall(rf"= {leaf}\S* parameter\(\d+\), sharding",
                              text)) == layers, leaf
        assert not re.findall(rf"= {leaf}\S* copy(-start)?\(", text), leaf
    if program == "decode":
        # two column writes and one attention a layer, no other kernel
        assert text.count("tpu_custom_call") == 3 * layers
        assert len(re.findall(
            r"%latent_decode_attention[.\d]* = [^\n]*? custom-call\(",
            text)) == layers
    else:
        # one flash kernel a layer; two grouped-product kernels (gate
        # and up in one) and the pass that brings them back an expert layer
        assert _calls("grouped_product", text) == 2 * (layers - 1)
        assert _calls("expert_combine", text) == layers - 1
        assert not re.findall(r"%ragged-dot", text)
        assert text.count("tpu_custom_call") >= layers + 3 * (layers - 1)


@pytest.mark.parametrize("program", ["decode", "prefill_16384"])
def test_window_full_engine_fits_and_updates_its_cache_in_place(
        chip, monkeypatch, capsys, program):
    """K-EXAONE-236B-A23B as the benchmark runs it (benchmark/configs/
    k-exaone-236b-a23b.json: layers 0-7 at full width, six window layers
    to two full ones, 8 of 128 experts, 32 slots of 16,384 positions,
    bfloat16 weights): the 32-row decode step and the prefill of the
    largest bucket compile for one v5e chip, arguments plus temporaries
    stay under its 16 GB (printed: run with ``-s``), and the result
    aliases every leaf of the donated cache - ``max_seq``-long keys and
    values on the two full layers, a ring of 128 positions on the six
    window layers, the expert layers' counters - and the token feed.
    The decode step writes its columns through ``kv_cache_write`` (two
    leaves a layer) and a full layer attends through
    ``ops/pallas/grouped_decode_attention`` (Mosaic takes it at 8 queries
    a key/value head and tiles of 512 positions); the prefill's full
    layers attend through the flash kernel, its window layers in XLA,
    and its pairs that are here are multiplied through
    ``ops/pallas/grouped_product``."""
    import json

    from benchmark.runners.serve_kexaone import build_model
    from horovod_tpu.serve.kv_cache import DecodeEngine

    def on_chip(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=chip), tree)

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "k-exaone-236b-a23b.json")) as f:
        cfg = json.load(f)["as_run"]
    full, window, slots, bucket, seq = 2, 6, 32, 16384, 16384
    model = build_model(cfg)
    params = on_chip(jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
                           )["params"]))
    monkeypatch.setattr(DecodeEngine, "_allocate_cache",
                        lambda self: on_chip(self._cache_shapes()))
    eng = DecodeEngine(model, params, num_slots=slots)
    assert eng.cache_bytes_by_kind() == {
        "kv": full * 2 * slots * seq * 8 * 128 * 2,         # 4.29 GB
        "compressed": 0, "state": 0,
        "ring": window * 2 * slots * 128 * 8 * 128 * 2,     # 0.10 GB
        "counter": (full + window - 1) * 3 * 8 * 4}
    assert eng._counts and "grouped_decode_attention" in eng.decode_kernels
    assert not {"decode_attention", "latent_decode_attention"} \
        & set(eng.decode_kernels)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=chip)

    if program == "decode":
        lowered = eng._decode_fn.lower(params, eng._cache, i32(slots),
                                       i32(slots))
    else:
        lowered = eng._prefill_fn(bucket).lower(
            params, eng._cache, i32(slots), i32(1, bucket), i32(), i32())
    compiled = lowered.compile()
    text, memory = compiled.as_text(), compiled.memory_analysis()
    with capsys.disabled():
        print(f"\nkexaone {program}: arguments "
              f"{memory.argument_size_in_bytes / 1e9:.3f} GB, temporaries "
              f"{memory.temp_size_in_bytes / 1e9:.3f} GB, aliased "
              f"{memory.alias_size_in_bytes / 1e9:.3f} GB")
    # the feed, and seven counters of 96 bytes in a tile each
    assert 0 < memory.alias_size_in_bytes - eng.cache_bytes() <= 16384
    assert _feed_is_aliased(text, params, eng._cache)
    assert (memory.argument_size_in_bytes + memory.temp_size_in_bytes
            < 16e9)
    row, ring = (rf"bf16\[{slots},8,128,{seq}\]",
                 rf"bf16\[{slots},8,128,128\]")
    for leaf, layers in ((row, full), (ring, window)):
        assert len(re.findall(rf"= {leaf}\S* parameter\(\d+\), sharding",
                              text)) == 2 * layers, leaf
        assert not re.findall(rf"= {leaf}\S* copy(-start)?\(", text), leaf
    if program == "decode":
        # two column writes a layer and one attention a full layer
        assert text.count("tpu_custom_call") == 2 * (full + window) + full
        assert len(re.findall(
            r"%grouped_decode_attention[.\d]* = [^\n]*? custom-call\(",
            text)) == full
    else:
        # one flash kernel a full layer; two grouped-product kernels and
        # one pass that brings them back an expert layer's turn, 4,096
        # tokens of the prompt at a time, under a conditional that a chunk
        # of padding does not take
        assert _calls("grouped_product", text) >= 2
        assert not re.findall(r"%ragged-dot", text)
        assert text.count("tpu_custom_call") >= full
        assert re.search(r"%expert_combine[.\d]* = [^\n]*? custom-call\(",
                         text)
        assert re.search(r" conditional\(", text)


@pytest.mark.parametrize("program", ["decode", "prefill_4096"])
def test_state_space_engine_fits_and_updates_its_cache_in_place(
        chip, monkeypatch, capsys, program):
    """granite-4.0-h-small as the benchmark runs it (benchmark/configs/
    granite-4.0-h-small.json: layers 0-9 at full width, nine Mamba-2
    layers to one full grouped-query layer, 36 of 72 experts on every
    layer, a tied head, 64 slots of 4,096 positions, bfloat16 weights):
    the 64-row decode step and the prefill of the largest bucket compile
    for one v5e chip, arguments plus temporaries stay under its 16 GB
    (printed: run with ``-s``), and the result aliases every leaf of the
    donated cache - a float32 state and a convolution tail a state-space
    layer, ``max_seq``-long keys and values on the full layer, every
    layer's expert counter - and the token feed. A decode step's
    state-space layer reads its state once and writes it once: XLA fuses
    the update and the read into one multi-output fusion a layer (a
    product for the read would be a third pass). The step's 640 pairs
    over 72 experts are at ``MASKED_PAIRS`` an expert: every held expert
    multiplies every row and no pair is sorted; the prefill's pairs that
    are here are multiplied through ``ops/pallas/grouped_product`` and its full layer
    attends through the flash kernel."""
    import json

    from benchmark import weights_granite
    from benchmark.runners.serve_granite import build_model
    from horovod_tpu.serve.kv_cache import DecodeEngine

    def on_chip(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=chip), tree)

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "granite-4.0-h-small.json")) as f:
        cfg = json.load(f)["as_run"]
    ssm_layers, slots, bucket, seq = 9, 64, 4096, 4096
    model = build_model(cfg)
    params = on_chip(jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
                           )["params"]))
    assert sum(x.size for x in jax.tree.leaves(params)) \
        == weights_granite.count(cfg) == 4_757_211_776
    monkeypatch.setattr(DecodeEngine, "_allocate_cache",
                        lambda self: on_chip(self._cache_shapes()))
    eng = DecodeEngine(model, params, num_slots=slots)
    assert eng.cache_bytes_by_kind() == {
        "kv": 2 * slots * seq * 8 * 128 * 2,                     # 1.07 GB
        "compressed": 0,
        "state": ssm_layers * slots * 128 * 64 * 128 * 4,        # 2.42 GB
        "conv": ssm_layers * slots * 3 * 8448 * 2,               # 0.03 GB
        "counter": (ssm_layers + 1) * 3 * 36 * 4}
    assert eng._counts and "grouped_decode_attention" in eng.decode_kernels
    assert not {"decode_attention", "latent_decode_attention"} \
        & set(eng.decode_kernels)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=chip)

    if program == "decode":
        lowered = eng._decode_fn.lower(params, eng._cache, i32(slots),
                                       i32(slots))
    else:
        lowered = eng._prefill_fn(bucket).lower(
            params, eng._cache, i32(slots), i32(1, bucket), i32(), i32())
    compiled = lowered.compile()
    text, memory = compiled.as_text(), compiled.memory_analysis()
    with capsys.disabled():
        print(f"\ngranite {program}: arguments "
              f"{memory.argument_size_in_bytes / 1e9:.3f} GB, temporaries "
              f"{memory.temp_size_in_bytes / 1e9:.3f} GB, aliased "
              f"{memory.alias_size_in_bytes / 1e9:.3f} GB")
    # the feed, and ten counters of 432 bytes in a tile each
    assert 0 < memory.alias_size_in_bytes - eng.cache_bytes() <= 32768
    assert _feed_is_aliased(text, params, eng._cache)
    assert 12.9e9 < memory.argument_size_in_bytes < 13.1e9
    assert (memory.argument_size_in_bytes + memory.temp_size_in_bytes
            < 16e9)
    state, tail, row = (rf"f32\[{slots},128,64,128\]",
                        rf"bf16\[{slots},25344\]",
                        rf"bf16\[{slots},8,128,{seq}\]")
    for leaf, leaves in ((state, ssm_layers), (tail, ssm_layers), (row, 2)):
        assert len(re.findall(rf"= {leaf}\S* parameter\(\d+\), sharding",
                              text)) == leaves, leaf
        assert not re.findall(rf"= {leaf}\S* copy(-start)?\(", text), leaf
    if program == "decode":
        assert memory.temp_size_in_bytes < 0.1e9
        # the state's update and its read are one fusion a layer: the
        # new state and the reduced output leave it together
        assert len(re.findall(
            rf"fusion[.\d]* = \(f32\[{slots},128,64\]\S*, {state}\S*\) "
            rf"fusion\(", text)) == ssm_layers
        # two column writes and one attention on the full layer, no
        # other kernel and no grouped product
        assert text.count("tpu_custom_call") == 3
        assert len(re.findall(
            r"%grouped_decode_attention[.\d]* = [^\n]*? custom-call\(",
            text)) == 1
        assert not re.findall(r"%ragged-dot", text)
    else:
        assert memory.temp_size_in_bytes < 1.8e9
        # one flash kernel; two grouped-product kernels and one pass that
        # brings them back to their tokens an expert layer: ``room`` holds
        # every pair of a layer that holds half the experts, so there is
        # one turn and no loop
        assert _calls("grouped_product", text) == 2 * (ssm_layers + 1)
        assert not re.findall(r"%ragged-dot", text)
        assert _calls("expert_combine", text) == ssm_layers + 1
        # the gather of the pairs' rows reads the tokens' activations
        # from VMEM (memory space 1), as the parent's did in its loop:
        # from HBM it takes 2.50 ms a layer against 0.51 (my chip runs,
        # PR 47). XLA holds nothing in VMEM across a Mosaic kernel, and
        # the activations live on past ``expert_combine``, so the rows
        # are gathered from a copy that dies at the gather
        # (models/hybrid.py ``pair_rows``: a stopgap that rests on this
        # reading until the way in has a kernel of its own, ROADMAP S20)
        shape = {m[1]: m[2] for m in re.finditer(
            r"\n\s*(?:ROOT )?(%[\w.\-]+) = (\S+)", text)}
        first = [m[1] for m in re.finditer(
            rf"= bf16\[{10 * bucket},4096\]\S* fusion\((%[\w.\-]+), [^\n]*?"
            r'op_name="[^"]*/moe/moe/gather"', text)]
        assert len(first) == ssm_layers + 1
        assert all("S(1)" in shape[x] for x in first), first


@pytest.mark.parametrize("program", ["decode", "prefill_2048"])
def test_block_diffusion_engine_fits_and_updates_its_cache_in_place(
        chip, monkeypatch, capsys, program):
    """SDAR-30B-A3B-Chat as the benchmark runs it (benchmark/configs/
    sdar-30b-a3b.json: layers 0-5 at full width, every layer full
    grouped-query attention with rotary positions and all 128 experts,
    an untied head over 151,936, block_len 4, 64 slots of 4,096
    positions, bfloat16 weights): the 64-row pass over 4 positions a row
    and the prefill of the largest bucket compile for one v5e chip,
    arguments plus temporaries stay under its 16 GB (printed: run with
    ``-s``), and the result aliases every leaf of the donated cache and
    both leaves of the feed (a row's block: ids and what is masked). A
    pass is, a layer, two writes of the block's four columns and one
    attention kernel over 32 query rows a key/value head; its 2,048 pairs
    over 128 experts are at ``MASKED_PAIRS`` an expert: every expert
    multiplies every row and no pair is sorted. The prefill attends
    through the flash kernel's block-causal form, groups its pairs
    through ``ops/pallas/grouped_product`` and runs no head."""
    import json

    from benchmark import weights_sdar
    from benchmark.runners.serve_sdar import build_model
    from horovod_tpu.serve.kv_cache import DecodeEngine

    def on_chip(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=chip), tree)

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "sdar-30b-a3b.json")) as f:
        cfg = json.load(f)["as_run"]
    layers, slots, bucket, seq, block = 6, 64, 2048, 4096, 4
    model = build_model(cfg)
    params = on_chip(jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
                           )["params"]))
    assert sum(x.size for x in jax.tree.leaves(params)) \
        == weights_sdar.count(cfg) == 4_361_055_744
    monkeypatch.setattr(DecodeEngine, "_allocate_cache",
                        lambda self: on_chip(self._cache_shapes()))
    eng = DecodeEngine(model, params, num_slots=slots)
    assert eng.cache_bytes_by_kind() == {
        "kv": layers * 2 * slots * seq * 4 * 128 * 2,            # 3.22 GB
        "compressed": 0, "state": 0, "counter": layers * 3 * 128 * 4}
    assert eng.block_len == block and eng.unmask == 2
    assert eng.decode_kernels == ("kv_cache_write_block",
                                  "grouped_decode_attention")
    feed = on_chip(jax.eval_shape(lambda: eng._feed))

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=chip)

    if program == "decode":
        lowered = eng._decode_fn.lower(params, eng._cache, feed,
                                       i32(2, slots))
    else:
        lowered = eng._prefill_fn(bucket).lower(
            params, eng._cache, feed, i32(1, bucket), i32(), i32(),
            i32(block), jax.ShapeDtypeStruct((block,), jnp.bool_,
                                             sharding=chip))
    compiled = lowered.compile()
    text, memory = compiled.as_text(), compiled.memory_analysis()
    with capsys.disabled():
        print(f"\nsdar {program}: arguments "
              f"{memory.argument_size_in_bytes / 1e9:.3f} GB, temporaries "
              f"{memory.temp_size_in_bytes / 1e9:.3f} GB, aliased "
              f"{memory.alias_size_in_bytes / 1e9:.3f} GB")
    # the feed's two leaves, and six counters of 1.5 KB in tiles
    assert 0 < memory.alias_size_in_bytes - eng.cache_bytes() <= 65536
    # jit drops the head from the prefill's arguments: nothing reads it
    unread = 0 if program == "decode" else 2048 * 151936 * 2
    assert 11.8e9 < memory.argument_size_in_bytes + unread < 12.1e9
    assert (memory.argument_size_in_bytes + memory.temp_size_in_bytes
            < 16e9)
    row = rf"bf16\[{slots},4,128,{seq}\]"
    assert len(re.findall(rf"= {row}\S* parameter\(\d+\), sharding",
                          text)) == 2 * layers
    assert not re.findall(rf"= {row}\S* copy(-start)?\(", text)
    if program == "decode":
        assert memory.temp_size_in_bytes < 1.0e9
        for kernel, calls in (("kv_cache_write_block", 2 * layers),
                              ("grouped_decode_attention", layers)):
            assert len(re.findall(
                rf"%{kernel}[.\d]* = [^\n]*? custom-call\(", text)) == calls
        assert not re.findall(r"%ragged-dot", text)
    else:
        assert memory.temp_size_in_bytes < 2.5e9
        assert text.count("tpu_custom_call") >= layers
        assert _calls("grouped_product", text) == 2 * layers
        assert not re.findall(r"%ragged-dot", text)
        # no head: nothing of the vocabulary's width is computed
        assert not re.findall(r"f32\[\d+(,\d+)*,151936\]", text)


def test_kernels_in_a_batch_sharded_step_on_four_chips(v5e):
    """What ``training.make_train_step`` builds on a four-chip host: one
    jit over the global mesh, batch sharded. XLA cannot partition a
    Mosaic kernel, so the bare call is refused; through
    ``shard_over_batch`` (how the models call them) the flash kernels and
    the BN+ReLU epilogue compile, each chip on its rows."""
    import functools

    import horovod_tpu as hvd

    hvd.shutdown()
    hvd.init(devices=v5e)
    try:
        rows = NamedSharding(hvd.mesh(), P(hvd.GLOBAL_AXES))
        everywhere = NamedSharding(hvd.mesh(), P())
        qkv = jax.ShapeDtypeStruct((16, 12, 1024, 64), BF16, sharding=rows)
        x = jax.ShapeDtypeStruct((32, 35, 35, 64), BF16, sharding=rows)
        c = jax.ShapeDtypeStruct((64,), F32, sharding=everywhere)

        def bare(q, k, v):
            return flash_attention(q, k, v, causal=True)

        with pytest.raises(NotImplementedError, match="partitioned"):
            jax.jit(bare).lower(qkv, qkv, qkv)

        def loss(q, k, v, x, s, b):
            attn = shard_over_batch(
                functools.partial(flash_attention, causal=True), (q, k, v))
            return (attn.astype(F32).sum()
                    + conv_bn_act.scale_bias_act(x, s, b).astype(F32).sum())

        text = jax.jit(jax.value_and_grad(loss, argnums=tuple(range(6)))) \
            .lower(qkv, qkv, qkv, x, c, c).compile().as_text()
        assert text.count("tpu_custom_call") >= 4   # 3 flash + 1 epilogue
        assert "all-reduce" in text   # the per-channel scale/bias gradients
    finally:
        hvd.shutdown()


@pytest.mark.parametrize(
    "chips,layers,builder",
    [(4, 3, "step"), (4, 1, "round"), (4, 2, "bare"), (1, 3, "step")],
    ids=["four-chips", "four-chips-round", "four-chips-bare", "one-chip"])
def test_train_step_issues_its_exchange_as_asynchronous_fusions(
        v5e, monkeypatch, chips, layers, builder):
    """``training.make_train_step`` over BERT-Large's layer at 3 of its
    24 layers. On the four described chips the step carries
    ``training._exchange_options`` and, by the compiled program's own
    text (``buckets.exchange_schedule``), every matrix of every layer,
    the MLP's ``wi`` and ``wo`` among them, the position table and both
    gradients of the tied table are reduced in asynchronous fusions, to
    the byte; only leaves under ``EXCHANGE_COMBINE_BYTES`` stay
    synchronous. ``make_train_round``'s scanned body takes the options
    the same way. Without them (``bare``: what the builder made until
    PR 40) all of it is synchronous. On one described chip the builder
    adds nothing: no option, and the program it hands the compiler is
    that of a bare ``jax.jit`` of the same body (compared as lowered,
    which costs no compile)."""
    import optax

    import horovod_tpu as hvd
    from horovod_tpu import training
    from horovod_tpu.models.transformer import Transformer, masked_lm_loss
    from horovod_tpu.parallel import buckets

    rows, seq = 16 * chips, 512
    hvd.shutdown()
    hvd.init(devices=v5e[:chips])
    try:
        everywhere = NamedSharding(hvd.mesh(), P())
        by_rows = NamedSharding(hvd.mesh(), P(hvd.GLOBAL_AXES))
        model = Transformer(vocab_size=30522, d_model=1024,
                            num_layers=layers, num_heads=16, d_ff=4096,
                            max_seq=seq, causal=False, dtype=BF16)
        opt = hvd.DistributedOptimizer(optax.adamw(1e-4))
        loss_fn = lambda logits, labels: masked_lm_loss(
            logits, labels[0], labels[1])
        tokens = jax.ShapeDtypeStruct((rows, seq), jnp.int32,
                                      sharding=by_rows)
        weights = jax.ShapeDtypeStruct((rows, seq), F32, sharding=by_rows)
        params = jax.eval_shape(
            lambda x: model.init(jax.random.PRNGKey(0), x, train=False),
            tokens)["params"]
        placed = lambda tree: jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=everywhere), tree)
        args = (placed(params), {}, placed(jax.eval_shape(opt.init, params)),
                tokens, (tokens, weights))

        assert (training._exchange_options(hvd.mesh()) is None) == (
            chips == 1)
        if builder == "bare":
            monkeypatch.setattr(training, "_exchange_options",
                                lambda mesh: None)
        if builder == "round":
            step, _ = training.make_train_round(model, opt, loss_fn=loss_fn,
                                                steps=2)
        else:
            step, _ = training.make_train_step(model, opt, loss_fn=loss_fn)
        if chips == 1:
            bare = jax.jit(
                training._make_one_step(model, opt, loss_fn),
                in_shardings=(everywhere,) * 3 + (by_rows,) * 2,
                out_shardings=(everywhere,) * 4, donate_argnums=(0, 1, 2))
            # traced from two call sites: the Mosaic kernels' serialized
            # bodies carry the call stack and are left out
            lowered = lambda f: re.sub(r'backend_config = "[^"]*"', "",
                                       f.lower(*args).as_text())
            assert lowered(step) == lowered(bare)
            return
        got = buckets.exchange_schedule(step.lower(*args).compile().as_text())
        # bf16 on the wire: four attention matrices and the MLP's two a
        # layer, the position table, the tied table's two gradients
        matrices = 2 * (layers * (4 * 1024 * 1024 + 2 * 1024 * 4096)
                        + seq * 1024 + 2 * 30522 * 1024)
        if builder == "bare":
            assert got["async_bytes"] == 0
            assert got["sync_bytes"] > matrices
            return
        assert got["async_bytes"] == matrices
        assert got["sync_bytes"] < training.EXCHANGE_COMBINE_BYTES
        assert got["reductions"] > 6 * layers + 3
    finally:
        hvd.shutdown()


def test_a_refused_exchange_option_names_where_it_comes_from(v5e):
    """A libtpu that has renamed one of ``_exchange_options``' names
    refuses it when the builder reads the mesh, with an error that says
    so, and not at the step's first call."""
    from horovod_tpu import training

    with pytest.raises(RuntimeError, match="training._exchange_options"):
        training._check_exchange_options(
            v5e[0], (("xla_enable_async_all_reduce", True),
                     ("xla_tpu_no_such_option", True)))
