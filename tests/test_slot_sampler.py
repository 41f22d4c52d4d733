"""``serving.engine.slot_sample_logits``: the sampled path (sort, softmax,
cumsum, filters, draw) runs under a ``lax.cond`` only in a call where a
row that counts samples; tokens are those of the unconditional form,
kept here as the plain reference, bit for bit. Cases of ONE parametrised
test: (a) all-greedy batches equal argmax; (b) mixed batches equal the
reference with the same keys; (c) the live mask decides whether a dead
sampled row selects the branch; (d) the decode programs hold their
``sort`` only inside a ``cond`` branch; (e) a toy paged engine counts
``sampled_steps`` from the host's mirrors."""
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny_config
from paddle_tpu.observability import tracing
from paddle_tpu.serving import ContinuousBatchingEngine, Server
from paddle_tpu.serving.engine import (build_paged_chunk_fn,
                                       build_slot_block_fn, init_slot_state,
                                       slot_sample_logits)
from paddle_tpu.serving.spec import build_spec_block_fn


def reference_sample(logits, keys, temperature, top_k, top_p):
    """The sampler as it stood before the conditional (PR 27's function
    body, unedited): every row pays the sorted path, greedy rows discard
    it."""
    S, V = logits.shape
    logits = logits.astype(jnp.float32)
    greedy = temperature <= 0.0
    greedy_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    t = jnp.where(greedy, jnp.float32(1.0),
                  temperature.astype(jnp.float32))
    scaled = logits / t[:, None]
    sorted_desc = jnp.sort(scaled, axis=-1)[:, ::-1]
    k = jnp.clip(top_k.astype(jnp.int32), 0, V)
    use_k = (k > 0) & (k < V)
    kth = jnp.take_along_axis(sorted_desc,
                              jnp.maximum(k - 1, 0)[:, None], axis=-1)
    kth = jnp.where(use_k[:, None], kth, -jnp.inf)
    filt = jnp.where(scaled < kth, -jnp.inf, scaled)
    sorted_f = jnp.where(sorted_desc < kth, -jnp.inf, sorted_desc)
    probs = jax.nn.softmax(sorted_f, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    cutoff_idx = jnp.clip(
        jnp.sum(cum < top_p[:, None], axis=-1, keepdims=True), 0, V - 1)
    cutoff = jnp.take_along_axis(sorted_f, cutoff_idx, axis=-1)
    cutoff = jnp.where((top_p < 1.0)[:, None], cutoff, -jnp.inf)
    filt = jnp.where(filt < cutoff, -jnp.inf, filt)
    sampled = jax.vmap(
        lambda kk, row: jax.random.categorical(kk, row))(keys, filt)
    return jnp.where(greedy, greedy_tok, sampled.astype(jnp.int32))


def _batch(S, V, seed, ties=False, dtype=jnp.float32):
    rs = np.random.RandomState(seed)
    x = rs.randn(S, V).astype(np.float32) * 3.0
    if ties:
        x = np.round(x)                 # many equal values, equal maxima
    logp = jax.nn.log_softmax(jnp.asarray(x), axis=-1).astype(dtype)
    keys = jax.vmap(jax.random.PRNGKey)(jnp.arange(S) + 17 * seed)
    return logp, keys


def _params(S, temp, top_k=0, top_p=1.0):
    full = lambda v, dt: jnp.asarray(np.broadcast_to(v, (S,)), dt)
    return full(temp, jnp.float32), full(top_k, jnp.int32), \
        full(top_p, jnp.float32)


def _takes_sampled_branch(temp, live=None):
    """Whether a call with these inputs selects the sampled branch,
    seen from outside: with an all-NaN-free batch both branches give a
    token, so the branch is read off the jaxpr's predicate instead —
    the value handed to the one ``cond``."""
    S = temp.shape[0]
    logp, keys = _batch(S, 32, 0)
    _, topk, topp = _params(S, 0.0)
    closed = jax.make_jaxpr(slot_sample_logits)(
        logp, keys, temp, topk, topp, live)
    conds = [e for e in closed.jaxpr.eqns if e.primitive.name == "cond"]
    assert len(conds) == 1
    pred_var = conds[0].invars[0]
    head = closed.jaxpr.replace(outvars=[pred_var])
    args = [logp, keys, temp, topk, topp] + ([] if live is None else [live])
    index = jax.core.eval_jaxpr(head, closed.consts,
                                *jax.tree.leaves(args))[0]
    return bool(index)


# -- (a), (b): tokens ---------------------------------------------------------

def _case_all_greedy(S, V, dtype=jnp.float32, ties=False):
    logp, keys = _batch(S, V, 1, ties=ties, dtype=dtype)
    temp, topk, topp = _params(S, 0.0, top_k=5, top_p=0.5)
    want = np.argmax(np.asarray(logp.astype(jnp.float32)), axis=-1)
    for fn in (slot_sample_logits, jax.jit(slot_sample_logits)):
        got = fn(logp, keys, temp, topk, topp)
        assert got.dtype == jnp.int32
        np.testing.assert_array_equal(np.asarray(got), want)
    assert not _takes_sampled_branch(temp)


def _case_mixed(temp, top_k=0, top_p=1.0, V=97, ties=False, live=None):
    S = len(temp)
    logp, keys = _batch(S, V, 2, ties=ties)
    temp, topk, topp = _params(S, np.asarray(temp), np.asarray(top_k),
                               np.asarray(top_p))
    if live is not None:
        live = jnp.asarray(live)
    want = np.asarray(jax.jit(reference_sample)(logp, keys, temp, topk, topp))
    got = np.asarray(jax.jit(slot_sample_logits)(
        logp, keys, temp, topk, topp, live))
    eager = np.asarray(slot_sample_logits(logp, keys, temp, topk, topp,
                                          live))
    rows = slice(None) if live is None else np.asarray(live)
    np.testing.assert_array_equal(got[rows], want[rows])
    np.testing.assert_array_equal(eager[rows], want[rows])
    assert _takes_sampled_branch(temp, live)


# -- (c): which rows count ----------------------------------------------------

def _case_dead_sampled_row(masked):
    temp = jnp.asarray([0.0, 0.9, 0.0, 0.0], jnp.float32)
    live = jnp.asarray([True, False, True, True])
    if masked:
        assert not _takes_sampled_branch(temp, live)
        # live rows still get their argmax; the dead row's pick is junk
        logp, keys = _batch(4, 32, 3)
        _, topk, topp = _params(4, 0.0)
        got = np.asarray(slot_sample_logits(logp, keys, temp, topk, topp,
                                            live))
        want = np.argmax(np.asarray(logp), axis=-1)
        np.testing.assert_array_equal(got[np.asarray(live)],
                                      want[np.asarray(live)])
    else:
        assert _takes_sampled_branch(temp)
        assert _takes_sampled_branch(temp, jnp.ones((4,), bool))


# -- (d): the programs' jaxprs ------------------------------------------------

def _sorts(closed):
    """(under a cond branch?, ...) for every ``sort`` in a jaxpr,
    sub-jaxprs (scan, jit, cond branches) included."""
    found = []

    def walk(jaxpr, under_cond):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "sort":
                found.append(under_cond)
            inside = under_cond or eqn.primitive.name == "cond"
            for v in eqn.params.values():
                for sub in v if isinstance(v, (list, tuple)) else (v,):
                    inner = getattr(sub, "jaxpr", sub)
                    if hasattr(inner, "eqns"):
                        walk(inner, inside)
    walk(closed.jaxpr, False)
    return found


def _toy_pure(all_positions=False):
    """A stand-in for the model's shared step: log-probs from the token
    embedding alone, the cache passed through."""
    def pure(pv, bv, toks, cf, pos, mask, pad, table=None, last=None):
        h = pv[toks if all_positions else toks[:, 0]]
        return jax.nn.log_softmax(h, axis=-1), cf
    return pure


def _case_sort_only_under_cond(program):
    S, V = 3, 24
    pv = jnp.ones((V, V), jnp.float32)
    state = dict(init_slot_state(S), table=jnp.zeros((S, 2), jnp.int32))
    cache = (jnp.zeros((S, 4), jnp.float32),)
    if program == "chunk":
        fn = build_paged_chunk_fn(_toy_pure(), 8)
        closed = jax.make_jaxpr(fn)(
            pv, (), jnp.zeros((1, 8), jnp.int32), cache,
            jnp.zeros((1, 2), jnp.int32), jnp.int32(0), jnp.int32(8),
            jax.random.PRNGKey(0), jnp.float32(0), jnp.int32(0),
            jnp.float32(1))
    elif program == "paged_block":
        fn = build_slot_block_fn(_toy_pure(), 4)
        closed = jax.make_jaxpr(fn)(pv, (), cache, state)
    else:
        fn = build_spec_block_fn(_toy_pure(all_positions=True), 2)
        closed = jax.make_jaxpr(fn)(
            pv, (), cache, state, jnp.zeros((S, 2), jnp.int32),
            jnp.zeros((S,), jnp.int32))
    sorts = _sorts(closed)
    assert sorts, "the sampled path lost its sort: update this test"
    assert all(sorts), "a sort runs outside the sampler's cond"


# -- (e): the engine's counter ------------------------------------------------

@functools.lru_cache(maxsize=None)
def _toy_paged_engine():
    paddle.seed(0)
    cfg = llama_tiny_config(tensor_parallel=False)
    model = LlamaForCausalLM(cfg)
    engine = ContinuousBatchingEngine(
        model, num_slots=3, max_len=64, decode_block=4, paged=True,
        block_size=8, prefill_chunk=8)
    return model, cfg, engine


def _case_sampled_steps(sampled_new):
    """``sampled_new``: the sampled request's max_new_tokens (None: an
    all-greedy run). Its first token comes from prefill, so it is live
    ``sampled_new - 1`` decode steps, whatever the greedy rows around
    it do and however long its retired slot waits for a refill."""
    model, cfg, engine = _toy_paged_engine()
    engine.reset()
    rs = np.random.RandomState(4)
    prompts = [rs.randint(0, cfg.vocab_size, (L,)).astype(np.int32)
               for L in (5, 9, 12, 7)]
    srv = Server(engine)
    t0 = time.perf_counter()
    rids = [srv.submit(p, max_new_tokens=mn)
            for p, mn in zip(prompts[:3], (14, 6, 11))]
    if sampled_new is not None:
        rids.append(srv.submit(prompts[3], max_new_tokens=sampled_new,
                               temperature=0.8, top_k=20, seed=5))
    res = srv.run_until_idle()
    assert all(len(res[r]) > 0 for r in rids)
    want = 0 if sampled_new is None else sampled_new - 1
    assert engine.sampled_steps == want
    assert srv.stats()["sampled_steps"] == want
    blocks = [s for s in tracing.since(t0)
              if s.name == "serving.decode_block"]
    assert blocks and all("kv_pages_live" in s.ids for s in blocks)
    assert sum(s.ids["sampled_steps"] for s in blocks) == want
    assert engine.steps == len(blocks) * engine.decode_block
    assert engine.decode_compile_count() == 1
    if sampled_new is not None:
        ref = model.generate(
            paddle.to_tensor(prompts[3][None, :]), max_new_tokens=sampled_new,
            do_sample=True, temperature=0.8, top_k=20, seed=5).numpy()[0]
        np.testing.assert_array_equal(res[rids[-1]], ref)


CASES = {
    "a-all-greedy-8x128": lambda: _case_all_greedy(8, 128),
    "a-all-greedy-1-row": lambda: _case_all_greedy(1, 64),
    "a-all-greedy-bf16-logits": lambda: _case_all_greedy(
        4, 256, dtype=jnp.bfloat16),
    "a-all-greedy-ties": lambda: _case_all_greedy(4, 64, ties=True),
    "b-greedy-and-temperature": lambda: _case_mixed([0.0, 1.0, 0.0, 0.7]),
    "b-top-k": lambda: _case_mixed([0.0, 1.0, 0.8, 1.3],
                                   top_k=[0, 5, 50, 1]),
    "b-top-p": lambda: _case_mixed([0.9, 0.0, 1.0, 0.5],
                                   top_p=[0.9, 0.5, 0.3, 0.99]),
    "b-top-k-and-top-p": lambda: _case_mixed(
        [1.0, 0.8, 0.0, 1.1], top_k=[10, 40, 3, 2],
        top_p=[0.9, 0.6, 0.9, 0.5]),
    "b-ties": lambda: _case_mixed([1.0, 0.0, 0.6, 1.0], top_k=[4, 4, 0, 9],
                                  top_p=[1.0, 1.0, 0.7, 0.8], ties=True),
    "b-top-k-0-and-over-V": lambda: _case_mixed(
        [1.0, 1.0, 1.0, 0.0], top_k=[0, 97, 1000, 1000]),
    "b-all-sampled-one-row": lambda: _case_mixed([0.8], top_k=[7],
                                                 top_p=[0.95]),
    "b-live-mask-on-mixed": lambda: _case_mixed(
        [0.0, 1.0, 0.9, 0.0], top_k=[0, 5, 0, 0],
        live=[True, True, False, True]),
    "c-dead-sampled-row-masked": lambda: _case_dead_sampled_row(True),
    "c-dead-sampled-row-no-mask": lambda: _case_dead_sampled_row(False),
    "d-sort-under-cond-chunk": lambda: _case_sort_only_under_cond("chunk"),
    "d-sort-under-cond-paged-block": lambda: _case_sort_only_under_cond(
        "paged_block"),
    "d-sort-under-cond-spec-block": lambda: _case_sort_only_under_cond(
        "spec"),
    "e-sampled-steps-one-sampled": lambda: _case_sampled_steps(10),
    "e-sampled-steps-short-sampled": lambda: _case_sampled_steps(3),
    "e-sampled-steps-all-greedy": lambda: _case_sampled_steps(None),
}


@pytest.mark.parametrize("case", list(CASES))
def test_slot_sampler(case):
    CASES[case]()
