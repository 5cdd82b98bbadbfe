"""What the check must refuse: the timed path broken underneath a run, and
the lower-precision control put in the program's place."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench_small import SEED, SMALL_LIMIT, run, small_cell
from bench import driver, frames, harness, model, reference  # noqa: E402
from bench import traffic  # noqa: E402


def _wrap(server, fused_out, top_out, active_in=lambda a: a):
    """Wrap the server's two step programs: `active_in` rewrites the rows
    a flush steps, `fused_out` / `top_out` rewrite what they return."""
    fused, top = server._fused_step, server.top_step

    def bad_fused(params, xbuf, payload, slots, cache, active):
        return fused_out(cache, *fused(params, xbuf, payload, slots, cache,
                                       active_in(active)))

    def bad_top(params, xbuf, cache, active):
        return top_out(cache, *top(params, xbuf, cache, active_in(active)))

    server._fused_step, server.top_step = bad_fused, bad_top


def _tamper_tokens(server):
    """A token altered where it is produced: every served token + 1."""
    _wrap(server, lambda _c, tok, xbuf, cache: (tok + 1, xbuf, cache),
          lambda _c, tok, cache: (tok + 1, cache))


def _tamper_state(server):
    """A step that returns its state unchanged: the arena never advances."""
    def keep(server_step):
        def step(*a):
            cache = a[-2]
            old = jax.tree.map(lambda x: x.copy(), cache)
            out = server_step(*a)
            return out[:-1] + (old,)
        return step

    server._fused_step = keep(server._fused_step)
    server.top_step = keep(server.top_step)


def _tamper_half(server):
    """Half of the batch left out: every other active row of a flush is
    not stepped (its arena row keeps its old state)."""
    def half(active):
        a = np.array(active)
        a[np.flatnonzero(a)[1::2]] = False
        return jnp.asarray(a)

    _wrap(server, lambda _c, *out: out, lambda _c, *out: out, half)


@pytest.mark.parametrize("tamper", [_tamper_tokens, _tamper_state,
                                    _tamper_half])
def test_broken_timed_path_is_not_correct(tamper):
    out = run(small_cell("qwen3-8b-l8.randtopk-chat"), tamper=tamper)
    assert not out["correct"]
    assert out["checks"]["logit_gap"]["value"] > SMALL_LIMIT


def _greedy_sessions(quant):
    """The small cell's seed pool, one session per script, each having
    served exactly the greedy tokens of the reference at `quant`."""
    cell = small_cell("qwen3-8b-l8.randtopk-chat")
    conf = cell["conf"]
    params = model.init_weights(conf, SEED)
    from repro.models.config import SplitConfig

    cfg = model.arch_config(conf).with_(
        split=SplitConfig(cut_layer=conf["cut_layer"]))
    plan = traffic.plan(cell["traffic"], conf["vocab_size"],
                        conf["serving"]["max_len"], SEED, 10.0)
    pool = frames.make_pool(cfg, params, plan)
    n = len(plan.prompt_len)
    sessions = [driver.Session(i + 1, i, plan.steps(i),
                               int(plan.prompt_len[i]), 0.0)
                for i in range(n)]
    ref = reference.Reference(conf, params, quant=quant)
    top_in, _, _ = harness._top_inputs(
        ref, plan.tokens, [(s, 0, s.steps) for s in sessions], pool)
    picked = ref.top_argmax(top_in)
    for s in sessions:
        s.served[:] = picked[s.script, :s.steps]
        s.done = True
    return conf, params, plan, pool, sessions


def test_lower_precision_control_is_not_correct():
    """The reference one precision below the configuration's, put in the
    program's place: sessions of the seed's pool that served exactly its
    greedy tokens. The check, on its own sample of them, refuses it."""
    conf, params, plan, pool, sessions = _greedy_sessions(
        reference.control_quant(
            small_cell("qwen3-8b-l8.randtopk-chat")["conf"]))
    chk = harness.check(conf, params, plan, pool, sessions,
                        {s.sid: (0, s.steps) for s in sessions}, SEED)
    assert chk["positions"] > 0
    assert chk["gap"] > SMALL_LIMIT


def test_check_reads_the_positions_served_in_the_window():
    """Tokens served before the window opened are not compared; one wrong
    token served inside it is."""
    conf, params, plan, pool, sessions = _greedy_sessions(None)
    vocab = conf["vocab_size"]
    window = {s.sid: (s.steps // 2, s.steps) for s in sessions}
    for s in sessions:
        lo = window[s.sid][0]
        s.served[:lo] = (s.served[:lo] + 1) % vocab
    chk = harness.check(conf, params, plan, pool, sessions, window, SEED)
    assert chk["positions"] == sum(
        hi - lo for s, lo, hi in harness._sample(
            [(s, *window[s.sid]) for s in sessions], [],
            np.random.default_rng([SEED, 0xC4EC])))
    assert chk["gap"] <= SMALL_LIMIT
    for s in sessions:
        s.served[-1] = (s.served[-1] + 1) % vocab
    chk = harness.check(conf, params, plan, pool, sessions, window, SEED)
    assert chk["gap"] > SMALL_LIMIT


def test_served_in_maps_replies_to_window_positions():
    r_t = np.array([0.5, 1.0, 1.5, 2.0, 1.2, 2.5, 3.0])
    r_sid = np.array([1, 1, 1, 1, 2, 2, 2])
    r_step = np.array([0, 1, 2, 3, 0, 1, 2])
    assert harness._served_in(r_t, r_sid, r_step, 1.0, 2.5) == {
        1: (1, 4), 2: (0, 1)}
