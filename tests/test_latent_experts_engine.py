"""The dense serving engine over Xing4.0-29B-A4B's toy configuration
(``tests/toy_models.py``): a cache of latents and rotary keys, the
device-resident expert counters that ``stats()`` alone reads, and a
decode step that never expands a latent. The mixers and the model
against its reference are ``tests/test_latent_experts.py``; what every
family promises behind the engine is ``tests/test_engine_contract.py``.
"""

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.runners import serve_xing
from horovod_tpu.serve.kv_cache import DecodeEngine
from toy_models import family, tokens, xing


def weights_and_model_sala():
    sala = family("sala")
    return sala.model, sala.params, 2


def test_the_latent_kernels_tiles_and_the_engines_counters():
    from horovod_tpu.ops.pallas import latent_attention

    assert [latent_attention.tile_of(n) for n in (8192, 1024, 512, 384,
                                                  100)] \
        == [1024, 1024, 512, 128, 100]
    # tiles read, tiles held, positions attended; a row that is not
    # active (-1) runs at position 0
    assert latent_attention.live_tiles([0, 5000, -1], 8192) == (7, 24, 5003)
    cfg, params, model = xing()
    engine = DecodeEngine(model, params, num_slots=2)
    assert "latent_decode_attention" in engine.decode_kernels
    assert "decode_attention" not in engine.decode_kernels
    assert engine.stats()["decode_positions_read"] == 0
    first, _ = engine.prefill(0, tokens(41).tolist())
    engine.decode([0], [first], [41]).collect()
    stats = engine.stats()
    assert stats["decode_positions_read"] == 42 + 1
    assert stats["decode_kv_read_share"] == 1.0     # 512 positions: a tile
    assert stats["decode_write_fused"] is None
    sala = DecodeEngine(*weights_and_model_sala())
    assert sala.stats()["decode_positions_read"] is None


def test_latent_cache_kinds_and_the_expert_counter():
    """The cache's only per-position leaves are the latent and the rotary
    key; the counter counts (token, expert) pairs of prompts' true
    tokens and of active decode rows, and nothing else."""
    cfg, params, model = xing()
    engine = DecodeEngine(model, params, num_slots=3)
    seq, layers, held = model.max_seq, cfg["num_layers"], 8
    assert engine.cache_bytes_by_kind() == {
        "kv": 0, "compressed": 0, "state": 0,
        "latent": layers * 3 * seq * (cfg["kv_rank"] + cfg["rope_dim"]) * 4,
        "counter": (layers - 1) * 3 * held * 4}
    names = {jax.tree_util.keystr(path[-1:]) for path, _ in
             jax.tree_util.tree_leaves_with_path(engine._cache)}
    assert names == {"['latent']", "['rope_key']", "['expert_counts']"}
    assert engine.stats()["expert_counts"] == np.zeros(
        (2, 3, held), int).tolist()
    engine.prefill(0, tokens(41).tolist()).collect()    # bucket 64
    engine.prefill(2, tokens(141).tolist()).collect()   # bucket 256
    counts = engine.expert_counts()
    assert counts.shape == (2, 3, held)
    assert (counts[:, 0].sum(axis=1) == (41 + 141) * cfg["top_k"]).all()
    assert not counts[:, 1:].any()
    for step in range(3):     # slot 1 is not active: not counted
        engine.decode([0, 2], [5, 7], [41 + step, 141 + step]).collect()
    counts = engine.expert_counts() - counts
    assert (counts[:, 0].sum(axis=1) == 3 * 2 * cfg["top_k"]).all()
    assert (counts[:, 2] == 3).all()
    assert (counts[:, 1] <= 3).all() and (counts[:, 1] <= counts[:, 0]).all()
    assert (counts[:, 1].sum(axis=1) >= 3 * cfg["top_k"]).all()
    assert engine.stats()["cache_donated"]
    assert DecodeEngine(*weights_and_model_sala()).stats()[
        "expert_counts"] is None


def test_the_expert_counter_wraps_and_differences_stay_right():
    """The counts run modulo 2**32 and are never reset: a reading before
    the wrap and one after differ, in uint32, by what was counted; and a
    reading builds no program."""
    from benchmark import harness

    cfg, params, model = xing()
    engine = DecodeEngine(model, params, num_slots=2)
    engine._cache = jax.tree_util.tree_map_with_path(
        lambda path, x: jnp.full_like(x, 2 ** 32 - 7)
        if engine.leaf_kind(path) == "counter" else x, engine._cache)
    compiles = harness.CompileCounter()
    before = engine.expert_counts()
    assert before.dtype == np.uint32 and (before == 2 ** 32 - 7).all()
    assert compiles.compiles == 0
    engine.prefill(1, tokens(41).tolist()).collect()
    after = engine.expert_counts()
    assert (after[:, 0] < before[:, 0]).any()          # wrapped
    assert ((after - before)[:, 0].sum(axis=1) == 41 * cfg["top_k"]).all()
    stats = {"engine": {"expert_counts": after.tolist()}}, \
        {"engine": {"expert_counts": before.tolist()}}
    assert (serve_xing.counted_between(stats[1], stats[0])[:, 0].sum(axis=1)
            == 41 * cfg["top_k"]).all()


def test_no_decode_step_expands_a_latent():
    """The decode program holds no tensor of per-head keys or values over
    the cache's positions: nothing of shape (.., heads, .., max_seq) with
    a head's key or value width."""
    cfg, params, model = xing()
    engine = DecodeEngine(model, params, num_slots=2)
    program = jax.make_jaxpr(engine._decode_impl)(
        params, engine._cache, engine._feed, jnp.zeros((2,), jnp.int32))
    heads, seq = cfg["num_heads"], model.max_seq
    widths = {cfg["nope_dim"], cfg["v_dim"], cfg["nope_dim"] + cfg["v_dim"],
              cfg["nope_dim"] + cfg["rope_dim"]}

    def shapes(jaxpr):
        for eqn in jaxpr.eqns:
            for var in eqn.outvars:
                yield tuple(getattr(var.aval, "shape", ()))
            for value in eqn.params.values():
                for sub in (value if isinstance(value, (tuple, list))
                            else (value,)):
                    inner = getattr(sub, "jaxpr", sub)
                    if hasattr(inner, "eqns"):
                        yield from shapes(inner)

    for shape in shapes(program.jaxpr):
        assert not (seq in shape and heads in shape
                    and widths & set(shape)), shape


def test_stats_reads_the_counters_while_the_engine_donates_them():
    """``stats()`` copies the expert counters out of a cache that every
    program donates: readers on other threads, more of them than cores
    care for and with a short switch interval, see no deleted array and
    counts that only grow, while the engine's own thread decodes."""
    import sys
    import threading

    cfg, params, model = xing()
    engine = DecodeEngine(model, params, num_slots=2)
    first, _ = engine.prefill(0, tokens(41).tolist())
    stop, errors = threading.Event(), []
    seen = [[] for _ in range(6)]

    def reader(mine):
        while not stop.is_set():
            try:
                mine.append(int(engine.expert_counts()[:, 0].sum()))
            except Exception as exc:        # reported below
                errors.append(exc)
                return

    threads = [threading.Thread(target=reader, args=(s,)) for s in seen]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for t in threads:
            t.start()
        for step in range(40):
            engine.decode([0], [first], [41 + step]).collect()
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=60)
        sys.setswitchinterval(interval)
    assert not errors, errors
    assert not any(t.is_alive() for t in threads)
    assert all(mine == sorted(mine) for mine in seen)
    assert sum(len(mine) for mine in seen) > 6
    layers = cfg["num_layers"] - 1
    assert int(engine.expert_counts()[:, 0].sum()) \
        == (41 + 40) * cfg["top_k"] * layers
    assert engine.stats()["cache_donated"]
