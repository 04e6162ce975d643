"""The paged engine's prepared step (ISSUE 30): a step launches the plan
made while the step before it ran, prepares the next one under it, and
collects. What it serves must not depend on any of that: for a fixed set
of requests the streams equal what the plain forward picks (no engine, no
page table), whichever steps were prepared, rebuilt or built in turn.

One case each in which a plan has to be thrown away (an EOS hit, cancel,
expiry, a NaN row, an injected fault, a pool too small, drain): the served
tokens, the ``plan``/``cause`` attributes of ``engine/step``,
``stats()["plan"]``, no block leaked, and no finished slot's K/V or state
written after its last token. ``gpt_tiny`` and the tiny hybrid decoder
(recurrent state beside K/V, routed experts: the token vector carries their
counts), on the CPU.
"""
import time

import numpy as np
import pytest

from test_qwen3_next import CHUNK, program
from test_qwen3_next_serving import reference_greedy

import paddle_tpu as paddle
from paddle_tpu.models import GPTForCausalLM, gpt_tiny
from paddle_tpu.monitor import trace
from paddle_tpu.serving import DecodeEngine, FaultSchedule, InjectedFault

KINDS = ["gpt", "hybrid"]
GEO = dict(max_slots=4, max_len=96, block_size=8, prefill_chunk=CHUNK)


@pytest.fixture(scope="module")
def models():
    paddle.seed(0)
    gpt = GPTForCausalLM(gpt_tiny(hidden_dropout_prob=0.0,
                                  attention_dropout_prob=0.0,
                                  use_flash_attention=False))
    gpt.eval()
    return {"gpt": (gpt, None, None), "hybrid": program()}


@pytest.fixture(scope="module")
def greedy(models):
    """What the plain forward picks, greedily, by request, with no engine
    and no page table: ``gpt`` through eager ``generate()`` (its own
    compiled loop over the contiguous cache with one scalar cursor),
    ``hybrid`` through a loop over the family's reference forward (no
    cache at all)."""
    served = {}

    def expect(kind, prompt, n):
        key = (kind, tuple(prompt), n)
        if key not in served:
            model, arrays, cfg = models[kind]
            if kind == "hybrid":
                served[key] = reference_greedy(arrays, cfg, prompt, n)
            else:
                ids = paddle.to_tensor(np.asarray([prompt], np.int32))
                out = model.generate(ids, max_new_tokens=n).numpy()
                served[key] = out[0, len(prompt):].tolist()
        return served[key]
    return expect


def paged(models, kind, **kw):
    return DecodeEngine(models[kind][0], **dict(GEO, **kw))


@pytest.fixture(scope="module")
def shared(models):
    """One engine a kind for the tests that leave theirs as they found it
    (idle, every block free, no prefix parked): an engine compiles two
    executables, which is four fifths of this file's time. Handed out with
    what its plan counters read, for ``plan_stats`` to subtract."""
    engines = {}

    def get(kind):
        if kind not in engines:
            engines[kind] = paged(models, kind)
        eng = engines[kind]
        assert eng.active_count == 0 and eng._plan is None \
            and eng._discarded is None and not eng.draining
        return eng, (dict(eng.plan_counts), dict(eng.plan_causes))
    return get


def plan_stats(eng, since=({}, {})):
    """``stats()["plan"]`` of the steps run since ``since`` was read."""
    st = eng.stats()["plan"]
    causes = {c: n - since[1].get(c, 0) for c, n in st["causes"].items()
              if n - since[1].get(c, 0)}
    return dict({h: st[h] - since[0].get(h, 0)
                 for h in ("prepared", "rebuilt", "sync")}, causes=causes)


def prompts_of(kind, lengths, seed):
    rng = np.random.default_rng(seed)
    vocab = 256 if kind == "gpt" else 512
    return [rng.integers(1, vocab, n).tolist() for n in lengths]


def plans(t0):
    """(plan, cause) of every engine step since ``t0`` that ran a call."""
    return [(s.attrs["plan"], s.attrs.get("cause"))
            for s in trace.spans(t0, time.perf_counter(), "engine/step")
            if "plan" in s.attrs]


def assert_nothing_leaked(eng, free_at_start):
    pg = eng._pager
    pg.check_invariants()
    assert (pg._ref == 0).all()
    eng.drop_prefix_cache()
    assert pg.free_blocks == free_at_start == pg.usable_blocks


def follows_the_full_forward(model, prompt, tokens):
    """``gpt``'s second control: one pass over prompt + tokens with no
    cache of any kind (the hybrid's first control is already that)."""
    ids = np.asarray([prompt + tokens], np.int32)
    logits = np.asarray(model(paddle.to_tensor(ids)).value())[0]
    for j, t in enumerate(tokens):
        row = logits[len(prompt) - 1 + j]
        second, best = np.sort(row)[-2:]
        assert best - second <= 1e-4 or t == int(row.argmax()), (j, t)


class Watch:
    """Steps an engine and, when a request leaves its slot, keeps what the
    slot held (its state rows, and the K/V blocks that are still nobody's)
    to compare after the steps that follow: nothing may write there."""

    def __init__(self, eng, reqs):
        self.eng, self.reqs = eng, list(reqs)
        self.held, self.kept = {}, []

    def _read(self, slot, blocks):
        out = []
        for layer, cache in zip(self.eng.spec.layers, self.eng._pools):
            for a in cache:
                kv = layer.kind == "kv"
                out.append((kv, np.asarray(a[blocks] if kv else a[slot])))
        return out

    def step(self):
        eng = self.eng
        for r in self.reqs:
            if r.slot is not None:
                self.held[r.id] = (r.slot, eng._pager.tables[r.slot].copy())
        was = {r.id for r in self.reqs if r.finished}
        out = eng.step()
        for slot, blocks, before, taken in self.kept:
            # a block handed to another tenant since is that tenant's
            taken.update(int(b) for b in blocks
                         if (eng._pager.tables == b).any())
            free = [i for i, b in enumerate(blocks) if int(b) not in taken]
            for (kv, x), (_, y) in zip(before, self._read(slot, blocks)):
                if kv:
                    x, y = x[free], y[free]
                np.testing.assert_array_equal(x, y)
        self.kept = [k for k in self.kept if eng._slot_req[k[0]] is None
                     and k[0] not in eng._prefilling]
        for r in self.reqs:
            if r.finished and r.id not in was and r.id in self.held:
                slot, row = self.held[r.id]
                blocks = row[row != 0]
                self.kept.append((slot, blocks, self._read(slot, blocks),
                                  set()))
        return out

    def run(self, max_steps=400):
        n = 0
        while not all(r.finished for r in self.reqs):
            self.step()
            n += 1
            assert n < max_steps
        self.step()
        self.step()


# ------------------------------------------------ what is served, undisturbed

@pytest.mark.parametrize("kind", KINDS)
def test_streams_equal_the_contiguous_engine_and_the_plain_forward(
        models, greedy, shared, kind):
    """Prompts shorter than, equal to and longer than a chunk, two that
    share a prefix with a third, more requests than slots (slots are taken
    again, each promoted by its final chunk while others decode), one that
    asks for a single token, and some that name an end token they never
    meet (they join the decode a step after their final chunk)."""
    lengths = (5, CHUNK, 40, 2 * CHUNK + 3, 3, 21, CHUNK + 1)
    prompts = prompts_of(kind, lengths, seed=1)
    stem = prompts[2][:24]
    prompts += [stem + p for p in prompts_of(kind, (4, 9), seed=2)]
    new = [7, 9, 12, 6, 1, 10, 8, 11, 5]
    plain = [greedy(kind, p, n) for p, n in zip(prompts, new)]
    vocab = 256 if kind == "gpt" else 512
    eos = [None if i % 3 else next(t for t in range(1, vocab)
                                   if t not in plain[i])
           for i in range(len(prompts))]
    eng, before = shared(kind)
    free = eng._pager.free_blocks
    hits = eng.stats()["paged"]["shared_hits"]
    t0 = time.perf_counter()
    reqs = [eng.submit(p, max_new_tokens=n, eos_token_id=e)
            for p, n, e in zip(prompts, new, eos)]
    Watch(eng, reqs).run()
    for p, r, want in zip(prompts, reqs, plain):
        assert r.status == "done" and r.tokens == want
        if kind == "gpt":
            follows_the_full_forward(models[kind][0], p, r.tokens)
    how = plans(t0)
    assert how[0] == ("sync", None)         # the engine was idle
    assert all(h == ("prepared", None) for h in how[1:]), how
    assert plan_stats(eng, before) == {
        "prepared": len(how) - 1, "rebuilt": 0, "sync": 1, "causes": {}}
    if kind == "gpt":
        assert eng.stats()["paged"]["shared_hits"] > hits
    assert_nothing_leaked(eng, free)


# ------------------------------------------- a plan that must be thrown away

def unforeseen(kind, case, eng, a, b, plain_a):
    """Make ``case`` happen to request ``a`` (``b`` decodes beside it);
    returns what ``a`` must end as: (status, its tokens are a prefix of the
    undisturbed stream of at most this many)."""
    if case == "stop":
        return "done", len(a.tokens) + 1      # its next token is its EOS
    if case == "cancel":
        assert eng.cancel(a) is True
        return "cancelled", len(a.tokens)
    if case == "expire":
        eng._clock = lambda: time.time() + 3600.0
        return "expired", len(a.tokens)
    if case == "nan":
        # poison the K (or the state) a's next step reads, and only a's
        row = eng._pager.tables[a.slot]
        eng._pools = [
            tuple(x.at[row[0] if layer.kind == "kv" else a.slot]
                  .set(np.nan) for x in cache)
            for layer, cache in zip(eng.spec.layers, eng._pools)]
        return "failed", len(a.tokens)
    if case == "drain":
        eng.begin_drain(grace_s=600.0)
        return "done", len(plain_a)
    raise AssertionError(case)


# (a NaN row of the hybrid decoder does not stay one row's: its routed
# layers combine the experts' outputs of all rows in one product, where
# 0 x NaN is NaN. That is the model's, and as it was.)
@pytest.mark.parametrize("kind,case", [
    (k, c) for k in KINDS for c in ("stop", "cancel", "expire", "nan",
                                    "drain") if (k, c) != ("hybrid", "nan")])
def test_a_discarded_plan_changes_nothing_that_is_served(
        models, greedy, shared, kind, case):
    # a stream that meets some token mid-way, and not before: its end token
    for seed in range(3, 40):
        pa, pb = prompts_of(kind, (19, 2 * CHUNK + 5), seed=seed)
        plain_a = greedy(kind, pa, 14)
        j = next((j for j in range(4, 13) if plain_a[j] not in plain_a[:j]),
                 None)
        if j is not None:
            break
    plain_b = greedy(kind, pb, 18)
    eos = plain_a[j] if case == "stop" else None
    # (a NaN stays in the pools and a drained engine stays shut: their own)
    eng, before = (paged(models, kind), ({}, {})) \
        if case in ("nan", "drain") else shared(kind)
    free = eng._pager.free_blocks
    a = eng.submit(pa, max_new_tokens=14, eos_token_id=eos,
                   deadline_s=900.0 if case == "expire" else None)
    b = eng.submit(pb, max_new_tokens=18)
    w = Watch(eng, [a, b])
    while len(a.tokens) < (j if case == "stop" else 4):
        w.step()
    assert eng._plan is not None        # the next step stands prepared
    t0 = time.perf_counter()
    status, most = unforeseen(kind, case, eng, a, b, plain_a)
    real = eng._clock
    w.step()
    if case in ("stop", "nan"):
        # seen at this step's collect: it ran as prepared, and the plan
        # made under it is the one that went
        assert plans(t0)[0] == ("prepared", None)
        assert eng._plan is None
        w.step()
    eng._clock = time.time if case == "expire" else real
    if case != "drain":
        assert a.status == status
    w.run()
    assert a.status == status and a.tokens == plain_a[:len(a.tokens)]
    assert len(a.tokens) <= most
    if case == "stop":
        assert a.tokens == plain_a[:j + 1]
    assert b.status == "done" and b.tokens == plain_b
    how = plans(t0)
    assert ("rebuilt", case) in how, how
    assert all(h[0] == "prepared" for h in how if h != ("rebuilt", case))
    st = plan_stats(eng, before)
    assert st["rebuilt"] == 1 and st["causes"] == {case: 1}
    if case == "nan":
        assert "non-finite" in a.error and eng.nan_logits == 1
    if case == "drain":
        assert eng.drained
    assert_nothing_leaked(eng, free)


@pytest.mark.parametrize("kind", KINDS)
def test_an_injected_fault_fails_the_step_and_the_next_is_built_anew(
        models, greedy, kind):
    pa, pb = prompts_of(kind, (19, CHUNK + 5), seed=4)
    eng = paged(models, kind,
                fault_schedule=FaultSchedule.parse("raise@decode:4"))
    free = eng._pager.free_blocks
    a = eng.submit(pa, max_new_tokens=12)
    b = eng.submit(pb, max_new_tokens=12)
    with pytest.raises(InjectedFault):
        eng.run()
    assert a.status == b.status == "failed"
    assert eng._plan is None and eng.live_count == 0
    eng._pager.check_invariants()
    t0 = time.perf_counter()
    again = eng.submit(pa, max_new_tokens=12)
    fin = eng.run()
    assert a in fin and b in fin            # the buffered terminals
    assert again.tokens == greedy(kind, pa, 12)
    how = plans(t0)
    assert how[0] == ("rebuilt", "fault")
    assert all(h == ("prepared", None) for h in how[1:])
    assert eng.stats()["plan"]["causes"] == {"fault": 1}
    assert_nothing_leaked(eng, free)


@pytest.mark.parametrize("kind", KINDS)
def test_a_pool_too_small_is_resolved_before_a_launch_never_under_one(
        models, greedy, kind):
    """Four requests whose growth outruns nine blocks: the plan made while
    a step runs is given up when it would need an eviction (``blocks``),
    the step is then built before its launch, where it preempts, and is
    followed by one built the same way (``preempt``). Preempted requests
    are served again from their prompts: the same tokens."""
    prompts = prompts_of(kind, (20, 20, 20, 20), seed=6)
    plain = [greedy(kind, p, 20) for p in prompts]
    eng = paged(models, kind, max_len=48, kv_blocks=9, prefill_chunk=8)
    free = eng._pager.free_blocks
    t0 = time.perf_counter()
    reqs = [eng.submit(p, max_new_tokens=20) for p in prompts]
    n = 0
    while not all(r.finished for r in reqs):
        eng.step()
        eng._pager.check_invariants()
        n += 1
        assert n < 600
    assert eng.preemptions >= 1
    assert [r.tokens for r in reqs] == plain
    causes = eng.stats()["plan"]["causes"]
    assert causes.get("blocks", 0) >= 1 and causes.get("preempt", 0) >= 1
    assert set(causes) <= {"blocks", "preempt"}
    assert {h for h in plans(t0) if h[0] == "rebuilt"} \
        == {("rebuilt", c) for c in causes}
    assert eng.stats()["plan"]["prepared"] >= 1
    assert_nothing_leaked(eng, free)


@pytest.mark.parametrize("kind", KINDS)
def test_a_seed_samples_the_same_stream_however_its_steps_were_built(
        models, kind):
    prompts = prompts_of(kind, (5, CHUNK + 2, 30), seed=7)

    def sampled(disturb):
        eng = paged(models, kind, do_sample=True, temperature=0.9, top_k=20,
                    seed=11)
        reqs = [eng.submit(p, max_new_tokens=10) for p in prompts]
        n = 0
        while not all(r.finished for r in reqs):
            if n in disturb:
                # throws the prepared plan away and nothing else: the
                # step is built again, with the keys drawn for the plan
                eng.drop_prefix_cache()
            eng.step()
            n += 1
        return [r.tokens for r in reqs], eng.stats()["plan"]

    calm, st = sampled(())
    assert st["rebuilt"] == 0
    stirred, st = sampled((2, 3, 7))
    assert st["causes"] == {"blocks": 3}
    assert stirred == calm
    assert len({tuple(t) for t in calm}) == 3
