"""The traffic generators: deterministic by seed, the stated sizes, group
size, turn counts and due times, and the same work for every seed."""
import numpy as np
import pytest

from bench.draw import pow2_bucket, quantiles
from bench.harness import BENCH_DIR, load_json, load_module
from bench.tests.fake_pool import FakePool

ENGINE = load_json(BENCH_DIR / "configs" / "mistral-7b-v0.1.json")["engine"]
VOCAB = 256000


def generator(mix_name, seed, **over):
    mix = dict(load_json(BENCH_DIR / "traffic" / f"{mix_name}.json"), **over)
    mod = load_module(BENCH_DIR / "traffic" / f"{mix['generator']}.py",
                      f"bench_traffic_{mix['generator']}")
    return mix, mod.make(mix, seed=seed, vocab=VOCAB, engine=ENGINE)


class Clock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t


def serve(gen, pool, clock, seconds, dt=0.05):
    """The harness's loop on a simulated clock: release, step, collect."""
    end = clock.t + seconds
    while clock.t < end:
        gen.release(pool, clock.t)
        if not pool.idle:
            pool.step()
            gen.collect(pool, pool.drain_requests())
        clock.t += dt


@pytest.mark.parametrize("dist", [
    {"dist": "uniform", "min": 3, "max": 5},
    {"dist": "lognormal", "median": 192, "sigma": 0.6, "min": 64, "max": 512},
    {"dist": "pareto", "alpha": 1.2, "min": 128, "max": 896},
    {"dist": "uniform", "min": 0.5, "max": 2.0, "float": True}])
def test_quantiles_stay_in_range_and_fill_it(dist):
    x = quantiles(dist, 400)
    assert x.min() >= dist["min"] and x.max() <= dist["max"]
    assert x.max() - x.min() > 0.5 * (dist["max"] - dist["min"])
    if dist["dist"] == "uniform" and not dist.get("float"):
        counts = np.bincount(x)[dist["min"]:]
        assert counts.max() - counts.min() <= 1      # equal shares


def test_exponential_gaps_have_the_stated_mean():
    gaps = quantiles({"dist": "exponential", "mean": 0.5, "float": True},
                     1000)
    assert abs(gaps.mean() - 0.5) < 0.02 and (gaps > 0).all()


def test_rollout_sizes_are_one_set_in_a_seeded_order():
    mix, a = generator("rollout", 2 ** 31 + 17)
    _, b = generator("rollout", 2 ** 31 + 17)
    _, c = generator("rollout", 5)
    assert a.sizes == b.sizes
    assert a.sizes != c.sizes and sorted(a.sizes) != [] and \
        sorted(p for p, _ in a.sizes) == sorted(p for p, _ in c.sizes)
    for p, m in a.sizes:
        assert mix["prompt_len"]["min"] <= p <= mix["prompt_len"]["max"]
        assert m <= mix["max_new"]["max"] and p + m <= ENGINE["max_seq"]
        assert m >= min(mix["max_new"]["min"], ENGINE["max_seq"] - p)


def test_rollout_keeps_a_group_queued_and_groups_of_sixteen():
    mix, gen = generator("rollout", 11)
    clock = Clock()
    pool = FakePool(clock)
    gen.start(pool, clock.t)
    want = ENGINE["slots"] + mix["queued_groups"] * mix["group_size"]
    assert sum(len(r) for _, r in pool.submitted) == want
    assert all(kind == "group" and len(r) == mix["group_size"]
               for kind, r in pool.submitted)
    lead = pool.submitted[0][1][0]
    assert len(lead.prompt_tokens) == mix["lead_in"]["prompt_len"]
    serve(gen, pool, clock, 60.0)
    assert gen.ready()
    unfinished = sum(1 for it in gen.items if not it.req.finished)
    assert unfinished >= want - mix["group_size"]
    for it in gen.items:
        assert it.due == it.released
        assert len(it.req.prompt_tokens) == it.ctx
        assert ((it.req.prompt_tokens >= 2)
                & (it.req.prompt_tokens < VOCAB)).all()
    kinds = [it.kind for it in gen.items if it.key >= 0][::mix["group_size"]]
    assert kinds[::mix["greedy_every"]] == ["greedy"] * len(
        kinds[::mix["greedy_every"]])
    assert kinds[1] == "sampled"


def test_rollout_check_sample_has_the_greedy_group_and_the_longest():
    mix, gen = generator("rollout", 11)
    clock = Clock()
    pool = FakePool(clock)
    gen.start(pool, clock.t)
    serve(gen, pool, clock, 120.0)
    t0 = 100.0
    assert gen.settled(t0, t0 + 1)
    sample = gen.check_sample(t0, t0 + 1)
    assert len(sample) == mix["check"]["requests"]
    assert any(s.greedy.all() for s in sample)
    done = [it for it in gen.items if it.req.finished and it.kind == "sampled"
            and it.key >= 0]
    longest = max(len(it.req.completion) for it in done)
    assert max(len(s.served) for s in sample if not s.greedy.any()) == longest


def test_sessions_are_one_set_in_a_seeded_order():
    mix, a = generator("agent", 2 ** 31 + 3)
    _, b = generator("agent", 2 ** 31 + 3)
    _, c = generator("agent", 4)
    key = lambda g: [(s.user_len, s.turns, s.tool_lens, s.reply_lens)
                     for s in g.sessions]
    assert key(a) == key(b) and key(a) != key(c)
    assert sorted(s.user_len for s in a.sessions) == \
        sorted(s.user_len for s in c.sessions)
    assert sorted(a.gaps) == pytest.approx(sorted(c.gaps))
    assert abs(np.mean(a.gaps) - 1.0 / mix["rate_per_s"]) < 0.1
    for s in a.sessions:
        assert mix["turns"]["min"] <= s.turns <= mix["turns"]["max"]
        assert mix["user_len"]["min"] <= s.user_len <= mix["user_len"]["max"]
        assert all(mix["tool_len"]["min"] <= t <= mix["tool_len"]["max"]
                   for t in s.tool_lens)
        assert all(mix["reply_len"]["min"] <= t <= mix["reply_len"]["max"]
                   for t in s.reply_lens)


def test_session_turns_are_due_after_the_tool_delay_and_fit():
    mix, gen = generator("agent", 9)
    clock = Clock()
    pool = FakePool(clock)
    gen.start(pool, clock.t)
    arrivals = [s.arrival for s in gen.sessions]
    assert arrivals == sorted(arrivals) and arrivals[0] > clock.t
    serve(gen, pool, clock, 40.0)
    assert max(len(pool.sessions), 0) <= mix["max_open_sessions"]
    by_session = {}
    for it in gen.items:
        by_session.setdefault(it.key, []).append(it)
    closed = [s for s in gen.sessions if s.closed_at is not None]
    assert closed and len(pool.closed) == len(closed)
    sys_len = mix["system_len"]
    for s in closed:
        items = by_session[s.index]
        assert 1 <= len(items) <= s.turns
        first = items[0]
        assert first.due == s.arrival and first.released >= first.due
        assert len(first.req.prompt_tokens) == sys_len + s.user_len
        assert any((first.req.prompt_tokens[:sys_len] == p).all()
                   for p in gen.system_prompts)
        for j in range(1, len(items)):
            prev, cur = items[j - 1], items[j]
            assert cur.due == pytest.approx(prev.req.last_token_ts
                                            + s.delays[j])
            assert len(cur.req.prompt_tokens) == s.tool_lens[j]
            assert cur.req.max_new_tokens == s.reply_lens[j]
        assert len(s.tokens) <= ENGINE["max_seq"]
        if len(items) < s.turns:            # ended early: the next turn
            assert not gen._fits(s)         # would not have fit
        seq_len = len(s.tokens)
        assert len(s.served) == sum(len(it.req.completion) for it in items)
        assert s.served[-1] == seq_len - 1


def test_sessions_wait_for_a_free_environment():
    mix, gen = generator("agent", 9, rate_per_s=50.0, max_open_sessions=2)
    clock = Clock()
    pool = FakePool(clock)
    gen.start(pool, clock.t)
    serve(gen, pool, clock, 30.0)
    assert len(gen.open) == 2 and len(gen.waiting) > 0
    firsts = [it for it in gen.items if it.req.max_new_tokens
              == gen.sessions[it.key].reply_lens[0] and it.due
              == gen.sessions[it.key].arrival]
    # a session that found no free environment is released late, and its
    # first turn stays due from its arrival
    assert max(it.released - it.due for it in firsts) > 1.0


def test_extend_buckets_stay_powers_of_two():
    mix, gen = generator("agent", 9)
    for s in gen.sessions[:50]:
        s.tokens = list(range(ENGINE["max_seq"] - 200))
        s.done_turns = 1
        fits = gen._fits(s)
        bucket = pow2_bucket(1 + s.tool_lens[1], ENGINE["min_prefill_bucket"])
        assert fits == (len(s.tokens) - 1 + bucket <= ENGINE["max_seq"]
                        and len(s.tokens) + s.tool_lens[1] + s.reply_lens[1]
                        <= ENGINE["max_seq"])
