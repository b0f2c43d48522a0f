"""The model families behind the dense serving engine, at toy sizes with
seeded weights, each beside its plain reference under ``benchmark/``
(not collected: what ``tests/test_hybrid_model.py``,
``tests/test_latent_experts.py``, ``tests/test_power_retention.py`` and
``tests/test_engine_contract.py`` share).

* ``sala``: MiniCPM-SALA, block-sparse attention beside lightning linear
  attention, against ``benchmark/reference_sala.py``; logits' standard
  deviation about 0.06, float32 against float32 measured 1e-7 to 3e-6.
* ``brumby``: Brumby-14B-Base, power retention, a cache of states alone,
  against ``benchmark/reference_brumby.py``; about 0.23, measured 2e-6
  to 1e-5 (``tests/test_power_retention.py`` says why it is the widest).
* ``xing``: Xing4.0-29B-A4B, latent attention, routed experts and
  hyper-connected streams, against ``benchmark/reference_xing.py``
  (``benchmark/configs/xing4-29b-a4b.json``'s toy sizes, three of its
  six layers: one dense, two with experts); about 0.23.
* ``kexaone``: K-EXAONE-236B-A23B, window and full grouped-query layers
  three to one with a ring cache on the window layers, norms on the
  sublayers' outputs and a share of the routed experts, against
  ``benchmark/reference_kexaone.py`` (``benchmark/configs/
  k-exaone-236b-a23b.json``'s toy sizes, all eight layers: a window of
  64 in a ring of 128); about 1.0.
* ``granite``: granite-4.0-h-small, Mamba-2 state-space layers nine to
  one beside a position-free grouped-query layer, a share of the routed
  experts (softmax router) on every layer and a tied head, against
  ``benchmark/reference_granite.py`` (``benchmark/configs/
  granite-4.0-h-small.json``'s toy sizes, all ten layers: a state and a
  convolution tail a state-space layer beside the full layer's rows);
  about 1.6.

* ``sdar``: SDAR-30B-A3B-Chat, generation by diffusion over blocks of 4
  on full grouped-query layers (rotary, QK-norm) with all the routed
  experts, against ``benchmark/reference_sdar.py`` (``benchmark/configs/
  sdar-30b-a3b.json``'s toy sizes, all six layers). It is no entry of
  :func:`family`: a step of its engine is a pass over a block and its
  prefill yields no token, so ``tests/test_block_diffusion.py`` has its
  cases and ``tests/test_engine_contract.py`` a block model of its own.

A new family adds its entry to :func:`family` and so joins every case of
``tests/test_engine_contract.py``; it does not copy them.
"""

import collections
import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import (reference_brumby, reference_granite,
                       reference_kexaone, reference_sala, reference_sdar,
                       reference_xing)
from benchmark import (weights_brumby, weights_granite, weights_kexaone,
                       weights_sala, weights_sdar, weights_xing)
from benchmark.runners import (serve_brumby, serve_granite, serve_kexaone,
                               serve_sala, serve_sdar, serve_xing)
from horovod_tpu import tracing
from horovod_tpu.models.transformer import Transformer

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEED = 7
VOCAB = 512


def tokens(n, seed=0):
    return np.random.default_rng(seed).integers(1, VOCAB, n)


# ----------------------------------------------------------------- SALA

SPARSE = dict(kernel=8, stride=4, block_size=16, topk=6, init_blocks=1,
              window_size=32, dense_len=128)
SALA_CFG = dict(vocab_size=VOCAB, d_model=128, d_ff=256, num_heads=4,
                num_kv_heads=2, head_dim=32,
                mixer_types=["minicpm4", "lightning-attn", "lightning-attn",
                             "lightning-attn"],
                layer_indices=[0, 1, 2, 3], published_depth=32, scale_emb=12,
                scale_depth=1.4, dim_model_base=32, rope_theta=10000,
                rms_norm_eps=1e-6, sparse=SPARSE, max_seq=1024,
                dtype="float32", param_dtype="bfloat16")
SALA_ALL = tuple(SALA_CFG["mixer_types"])

_sala_forward = jax.jit(reference_sala.forward, static_argnums=(2, 3))


@functools.lru_cache(maxsize=None)
def sala_weights(kinds):
    cfg = dict(SALA_CFG, mixer_types=list(kinds),
               layer_indices=list(range(len(kinds))))
    return cfg, weights_sala.make_params(cfg, SEED)


def sala_reference(cfg, params, toks, precision="f32"):
    return np.asarray(_sala_forward(params, jnp.asarray(toks, jnp.int32),
                                    reference_sala.frozen(cfg), precision))


# --------------------------------------------------------------- Brumby

BRUMBY_CFG = dict(vocab_size=VOCAB, d_model=128, d_ff=256, num_heads=4,
                  num_kv_heads=2, head_dim=32, num_layers=2,
                  layer_indices=[0, 1], published_depth=40,
                  rope_theta=1000000, rms_norm_eps=1e-6, retention_eps=1e-6,
                  dim_model_base=None, max_seq=1024, dtype="float32",
                  param_dtype="bfloat16")

_brumby_forward = jax.jit(reference_brumby.forward, static_argnums=(2, 3))


@functools.lru_cache(maxsize=None)
def brumby_weights():
    return weights_brumby.make_params(BRUMBY_CFG, SEED)


def brumby_reference(toks, precision="f32"):
    return np.asarray(_brumby_forward(
        brumby_weights(), jnp.asarray(toks, jnp.int32),
        reference_brumby.frozen(BRUMBY_CFG), precision))


# ----------------------------------------------------------------- Xing

def xing_cfg(dtype="float32", **changes):
    with open(os.path.join(REPO, "benchmark", "configs",
                           "xing4-29b-a4b.json")) as f:
        published = json.load(f)
    cfg = dict(published["as_run"], **published["rehearse"])
    cfg.update(num_layers=3, layer_indices=[0, 2, 3], max_seq=512,
               dtype=dtype, param_dtype=dtype)
    cfg.update(changes)
    return cfg


@functools.lru_cache(maxsize=None)
def xing(dtype="float32"):
    cfg = xing_cfg(dtype)
    return cfg, weights_xing.make_params(cfg, SEED), \
        serve_xing.build_model(cfg)


_xing_forward = jax.jit(reference_xing.forward, static_argnums=(2, 3))


def xing_reference(cfg, params, toks, precision="f32"):
    return np.asarray(_xing_forward(params, jnp.asarray(toks, jnp.int32),
                                    reference_xing.frozen(cfg), precision))


# -------------------------------------------------------------- K-EXAONE

def kexaone_cfg(dtype="float32", **changes):
    with open(os.path.join(REPO, "benchmark", "configs",
                           "k-exaone-236b-a23b.json")) as f:
        published = json.load(f)
    cfg = dict(published["as_run"], **published["rehearse"])
    cfg.update(max_seq=512, dtype=dtype, param_dtype=dtype)
    cfg.update(changes)
    return cfg


@functools.lru_cache(maxsize=None)
def kexaone(**changes):
    cfg = kexaone_cfg(**changes)
    return cfg, weights_kexaone.make_params(cfg, SEED), \
        serve_kexaone.build_model(cfg)


_kexaone_forward = jax.jit(reference_kexaone.forward,
                           static_argnums=(2, 3, 5))


def kexaone_reference(cfg, params, toks, precision="f32", fault=None):
    return np.asarray(_kexaone_forward(
        params, jnp.asarray(toks, jnp.int32), reference_kexaone.frozen(cfg),
        precision, None, fault))


# --------------------------------------------------------------- granite

def granite_cfg(dtype="float32", **changes):
    with open(os.path.join(REPO, "benchmark", "configs",
                           "granite-4.0-h-small.json")) as f:
        published = json.load(f)
    cfg = dict(published["as_run"], **published["rehearse"])
    cfg.update(max_seq=512, dtype=dtype, param_dtype=dtype)
    cfg.update(changes)
    return cfg


@functools.lru_cache(maxsize=None)
def granite(**changes):
    cfg = granite_cfg(**changes)
    return cfg, weights_granite.make_params(cfg, SEED), \
        serve_granite.build_model(cfg)


_granite_forward = jax.jit(reference_granite.forward, static_argnums=(2, 3))


def granite_reference(cfg, params, toks, precision="f32"):
    return np.asarray(_granite_forward(
        params, jnp.asarray(toks, jnp.int32), reference_granite.frozen(cfg),
        precision))


# ------------------------------------------------------------------ SDAR

def sdar_cfg(dtype="float32", **changes):
    with open(os.path.join(REPO, "benchmark", "configs",
                           "sdar-30b-a3b.json")) as f:
        published = json.load(f)
    cfg = dict(published["as_run"], **published["rehearse"])
    cfg.update(max_seq=512, dtype=dtype, param_dtype=dtype)
    cfg.update(changes)
    return cfg


@functools.lru_cache(maxsize=None)
def sdar(**changes):
    cfg = sdar_cfg(**changes)
    return cfg, weights_sdar.make_params(cfg, SEED), \
        serve_sdar.build_model(cfg)


_sdar_forward = jax.jit(reference_sdar.forward, static_argnums=(2, 3))


def sdar_reference(cfg, params, toks, precision="f32"):
    return np.asarray(_sdar_forward(
        params, jnp.asarray(toks, jnp.int32), reference_sdar.frozen(cfg),
        precision))


# ------------------------------------------------- what the engine serves

# ``reference(toks, precision="f32")``: the plain reference's logits for
# one sequence; ``tol``: float32 against float32 on the CPU; ``no_pages``:
# what the paged engine says of a cache it cannot page (None: untested)
Family = collections.namedtuple(
    "Family", "name cfg params model reference tol no_pages")
FAMILIES = ("sala", "brumby", "xing", "kexaone", "granite")


@functools.lru_cache(maxsize=None)
def family(name):
    if name == "sala":
        cfg, params = sala_weights(SALA_ALL)
        return Family(name, cfg, params, serve_sala.build_model(cfg),
                      functools.partial(sala_reference, cfg, params), 2e-5,
                      "key/value models only")
    if name == "brumby":
        return Family(name, BRUMBY_CFG, brumby_weights(),
                      serve_brumby.build_model(BRUMBY_CFG),
                      brumby_reference, 5e-5,
                      "has no paged cache .* recurrent state")
    if name == "xing":
        cfg, params, model = xing()
        return Family(name, cfg, params, model,
                      functools.partial(xing_reference, cfg, params), 2e-5,
                      None)
    if name == "kexaone":
        cfg, params, model = kexaone()
        return Family(name, cfg, params, model,
                      functools.partial(kexaone_reference, cfg, params),
                      5e-5, None)
    if name == "granite":
        cfg, params, model = granite()
        return Family(name, cfg, params, model,
                      functools.partial(granite_reference, cfg, params),
                      5e-5, "key/value models only")
    raise KeyError(name)


def prefill_spans(began):
    """The ``engine.prefill`` spans written since ``began``."""
    return [s for s in tracing.spans()
            if s["name"] == "engine.prefill" and s["t"] >= began]


def step_logits(engine, step_tokens, positions):
    """One teacher-forced decode step over all of the engine's rows, as
    ``_decode_impl`` runs it, returning the logits it would take the
    argmax of. A row at position -1 is not active: it runs token 0 at
    position 0, and a model that counts is told so. One program an
    engine, not one a call: a teacher-forced case takes forty steps."""
    if not hasattr(engine, "step_for_tests"):
        model = engine._model
        counts = bool(getattr(model, "counts_active_rows", False))
        engine.step_for_tests = jax.jit(lambda p, c, t, q: model.apply(
            {"params": p, "cache": c}, jnp.where(q >= 0, t, 0)[:, None],
            positions=jnp.maximum(q, 0), train=False, mutable=["cache"],
            **({"active": q >= 0} if counts else {})))
    logits, mutated = engine.step_for_tests(
        engine._params, engine._cache, jnp.asarray(step_tokens, jnp.int32),
        jnp.asarray(positions, jnp.int32))
    engine._cache = mutated["cache"]
    return np.asarray(logits[:, 0])


def toy_transformer(max_seq):
    model = Transformer(vocab_size=61, d_model=32, num_layers=2,
                        num_heads=2, d_ff=64, max_seq=max_seq, causal=True,
                        dtype=jnp.float32)
    return model, model.init(jax.random.PRNGKey(0),
                             jnp.zeros((1, 8), jnp.int32),
                             train=False)["params"]


@functools.lru_cache(maxsize=None)
def _forward(model):
    return jax.jit(lambda params, toks: model.apply(
        {"params": params}, toks, train=False))


def uncached_greedy(model, params, prompt, n):
    """Reference: a full (cache-free) forward per token, greedy argmax.
    Every length runs the one program of ``max_seq`` positions: row
    ``len - 1`` of a causal model does not see the padding after it."""
    forward = _forward(model)
    toks = list(prompt)
    padded = np.zeros((1, model.max_seq), np.int32)
    out = []
    for _ in range(n):
        padded[0, :len(toks)] = toks
        logits = forward(params, padded)
        out.append(int(jnp.argmax(logits[0, len(toks) - 1])))
        toks.append(out[-1])
    return out
