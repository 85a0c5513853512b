"""The sampler of the paged families (`paged_lm.PagedLM._sample`, ISSUE 60): the
top 8 from group maxima is `jax.lax.top_k` of the whole row, ties included; the
log-probabilities of those eight are `log_softmax`'s; the greedy token is the
first; a drawn token is the one the body it had until PR 59 drew (that body is
kept here as the reference); and, through the engine on a toy, the Gumbel draw
is made on the steps that hold a LIVE lane with `temperature > 0` and on no
other (`gen_sample_steps_total{path=}`), with a request's answer what the parent
commit answered."""

from __future__ import annotations

import asyncio
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests import test_hybrid_conv as toy
from tpuserve.models.paged_lm import LOGPROBS, TOP_GROUP, PagedLM


def parent_sample(logits, seed, position, temp):
    """`_sample` as it was until PR 59 (1cd84b5): the reference."""
    def one(lg, sd, pos, t):
        key = jax.random.fold_in(jax.random.fold_in(jax.random.key(0), sd), pos)
        g = jax.random.gumbel(key, lg.shape, jnp.float32)
        sampled = jnp.argmax(lg / jnp.where(t > 0, t, 1.0) + g)
        return jnp.where(t > 0, sampled, jnp.argmax(lg)).astype(jnp.int32)

    tok = jax.vmap(one)(logits, seed, position, temp)
    lp, ids = jax.lax.top_k(jax.nn.log_softmax(logits, axis=-1), LOGPROBS)
    return tok, ids.astype(jnp.int32), lp


ROWS = 6   # no whole tile of eight rows; 16: two (`_top_logits` views the logits by tile)


def rows_of(kind: str, v: int, rows: int = ROWS) -> jax.Array:
    """(rows, v) float32 logits of one kind."""
    rng = np.random.default_rng(v + rows)
    lg = 3.0 * rng.standard_normal((rows, v)).astype(np.float32)
    n = -(-v // TOP_GROUP)
    if kind == "halves":        # a few dozen distinct values: ties cross the groups
        lg = np.round(lg * 2) / 2
    elif kind == "equal":
        lg[:] = 1.25
    elif kind == "minus_inf":   # a column no row may pick, and the largest next to it
        lg[:, v // 2] = -np.inf
        lg[:, v // 2 + 1] = 40.0
    elif kind == "one_group":   # the eight largest side by side, four of them equal
        at = (n // 2) * TOP_GROUP + np.arange(3, 3 + 2 * LOGPROBS, 2)
        lg[:, at] = np.asarray([50, 51, 50, 50, 52, 50, 53, 54], np.float32)
    elif kind == "eight_groups":   # one of the eight largest a group, as far as there are groups
        at = np.linspace(1, v - 2, LOGPROBS).astype(int)
        lg[:, at] = np.asarray([50, 51, 50, 50, 52, 50, 53, 54], np.float32)
    return jnp.asarray(lg)


@pytest.mark.parametrize("kind", ["normal", "halves", "equal", "minus_inf", "one_group",
                                  "eight_groups"])
@pytest.mark.parametrize("v", [320, 1000, 19072, 65536])
@pytest.mark.parametrize("rows", [ROWS, 16])
def test_the_grouped_top_8_is_top_k_of_the_whole_row(rows, v, kind):
    lg = rows_of(kind, v, rows)
    want_lp, want_ids = jax.lax.top_k(jax.nn.log_softmax(lg, axis=-1), LOGPROBS)
    tok, ids, lp = jax.jit(PagedLM._sample)(
        lg, jnp.zeros(rows, jnp.int32), jnp.zeros(rows, jnp.int32), jnp.zeros(rows), False)
    assert ids.dtype == jnp.int32 and tok.dtype == jnp.int32
    np.testing.assert_array_equal(ids, want_ids)
    np.testing.assert_array_equal(tok, jnp.argmax(lg, axis=-1))
    np.testing.assert_allclose(lp, want_lp, atol=1e-6, rtol=0)


@pytest.mark.parametrize("temps", [(0.0, 0.7, 0.0, 1.3, 0.2, 0.0), (0.0,) * 6, (0.9,) * 6],
                         ids=["mixed", "greedy", "drawn"])
@pytest.mark.parametrize("v", [320, 1000, 4096])
def test_tokens_are_what_the_parents_body_gave(v, temps):
    lg = rows_of("normal", v)
    seed = jnp.asarray([3, 3, 11, 2 ** 31 - 1, 0, 7], jnp.int32)
    position = jnp.asarray([5, 6, 5, 900, 0, 41], jnp.int32)
    temp = jnp.asarray(temps, jnp.float32)
    want = jax.jit(parent_sample)(lg, seed, position, temp)
    got = jax.jit(PagedLM._sample)(lg, seed, position, temp, jnp.any(temp > 0))
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)
    greedy = np.asarray(temps) == 0
    np.testing.assert_array_equal(np.asarray(got[0])[greedy],
                                  np.asarray(jnp.argmax(lg, axis=-1))[greedy])
    if any(temps):   # the draws are draws: another seed or position, another token somewhere
        other = jax.jit(PagedLM._sample)(lg, seed + 1, position, temp, True)
        assert (np.asarray(other[0]) != np.asarray(got[0]))[~greedy].any()


def test_no_random_number_is_made_unless_the_launch_draws():
    """The draw lives in ONE branch of a `cond`: outside it the program has no
    random bits at all, and a launch whose predicate is False keeps the first of
    the top 8 whatever a (stale) temperature says."""
    lg = rows_of("normal", 1000)
    i32 = jnp.zeros(ROWS, jnp.int32)
    jaxpr = jax.make_jaxpr(PagedLM._sample)(lg, i32, i32, jnp.ones(ROWS), True)
    conds = [e for e in jaxpr.eqns if e.primitive.name == "cond"]
    assert len(conds) == 1
    assert not any("random" in str(e) or "threefry" in str(e) for e in jaxpr.eqns
                   if e.primitive.name != "cond")
    texts = [str(b) for b in conds[0].params["branches"]]
    assert sum("random" in t or "threefry" in t for t in texts) == 1
    tok, _ids, _lp = PagedLM._sample(lg, i32, i32, jnp.ones(ROWS), False)
    np.testing.assert_array_equal(tok, jnp.argmax(lg, axis=-1))


# -- through the engine ---------------------------------------------------------------------------

# What the parent commit (1cd84b5) answered these bodies with on the toy below.
BODIES = {
    "greedy": {"prompt_ids": toy.PROMPTS[0].tolist(), "max_new_tokens": 12, "logprobs": 3},
    "drawn": {"prompt_ids": toy.PROMPTS[1].tolist(), "max_new_tokens": 3, "logprobs": 2,
              "temperature": 0.7, "seed": 5},
}
PARENT = {
    "greedy": {
        "tokens": [77, 55, 27, 82, 17, 72, 0, 20, 20, 64, 26, 64],
        "ids": [[77, 48, 18], [55, 81, 86], [27, 91, 60], [82, 31, 57], [17, 63, 36],
                [72, 54, 75], [0, 68, 74], [20, 71, 77], [20, 9, 89], [64, 27, 54],
                [26, 78, 77], [64, 20, 53]],
        "values": [[-3.019607, -3.035985, -3.043410], [-1.970873, -2.671244, -2.865484],
                   [-2.915109, -3.118536, -3.183384], [-2.740943, -2.876318, -2.881676],
                   [-2.336386, -3.116087, -3.224539], [-2.562242, -2.790708, -2.864581],
                   [-2.756835, -2.780615, -2.945921], [-2.514274, -2.727554, -3.120270],
                   [-1.950513, -2.811388, -2.954754], [-1.834415, -2.797119, -3.145918],
                   [-2.958426, -3.094017, -3.098817], [-2.652094, -3.028569, -3.071868]]},
    "drawn": {
        "tokens": [87, 65, 48], "ids": [[51, 20], [95, 57], [59, 63]],
        "values": [[-2.457325, -2.738522], [-2.861232, -3.211084], [-2.118152, -2.272816]]},
}


def is_the_parents(got: dict, want: dict) -> None:
    assert got["tokens"] == want["tokens"] and got["n_tokens"] == len(want["tokens"])
    assert got["logprobs"]["ids"] == want["ids"]
    np.testing.assert_allclose(got["logprobs"]["values"], want["values"], atol=2e-5, rtol=0)


def through_the_engine(tmp_path, bodies: list[dict]):
    from tpuserve.config import GenserveConfig
    from tpuserve.genserve import GenEngine
    from tpuserve.obs import Metrics
    from tpuserve.runtime import build_runtime

    model = toy.make_model(tmp_path, name="eng")
    rt = build_runtime(model, compile_forward=False)
    metrics = Metrics()
    eng = GenEngine(model, rt, metrics, GenserveConfig(
        slots=toy.SLOTS, kv_paging=True, kv_page_tokens=toy.PAGE, prefill_chunk=toy.CHUNK))
    eng.compile()
    model.bind_metrics(metrics)

    async def go():
        await eng.start()
        out = await asyncio.gather(*[eng.submit(model.host_decode(
            json.dumps(b).encode(), "application/json")) for b in bodies])
        await eng.stop()
        return out

    loop = asyncio.new_event_loop()
    try:
        results = loop.run_until_complete(go())
    finally:
        loop.close()
    c = metrics.counter_values()
    return results, {path: c.get(f"gen_sample_steps_total{{model=eng,path={path}}}", 0)
                     for path in ("greedy", "drawn")}, \
        c["moe_layers_total{model=eng,phase=decode}"] / 5   # five routed layers a step


def test_greedy_requests_draw_on_no_step(tmp_path):
    results, steps, ran = through_the_engine(
        tmp_path, [BODIES["greedy"], dict(BODIES["greedy"], max_new_tokens=4)])
    assert steps == {"greedy": ran, "drawn": 0} and ran >= 11
    is_the_parents(results[0], PARENT["greedy"])


def test_the_draw_is_made_while_a_drawing_lane_is_live_and_on_no_other_step(tmp_path):
    """The drawing request is live for its two steps (its first token is the
    launch's); then its lane is done, its temperature still in the state beside
    the greedy lane that decodes nine steps more: those are greedy steps."""
    results, steps, ran = through_the_engine(tmp_path, [BODIES["greedy"], BODIES["drawn"]])
    assert steps == {"greedy": ran - 2, "drawn": 2} and ran >= 11
    is_the_parents(results[0], PARENT["greedy"])
    is_the_parents(results[1], PARENT["drawn"])
