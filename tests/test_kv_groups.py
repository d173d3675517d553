"""The block manager over layers of two kinds: one pool and one free list,
one block table a group, window groups that give blocks back while the
sequence lives, and a prefix index whose entries stand only while every
group's copy does (`serve/engine/kv_manager.py`)."""

import random

import pytest

from ray_tpu.serve.engine import KVBlockManager, KVCacheExhausted, Scheduler, Sequence

BS, W = 8, 32
GROUPS = (0, W, W, W)          # one global group, three window groups


def _mgr(num_blocks=200, windows=GROUPS, bs=BS, **kw):
    return KVBlockManager(num_blocks, bs, group_windows=windows, **kw)


def _prefill(m, sid, prompt, chunk=16, new=1):
    """Admit and prefill as the scheduler does: slide, compute, register."""
    _, cached = m.allocate_cached(sid, prompt, len(prompt) + new)
    start = cached
    while start < len(prompt):
        n = min(chunk, len(prompt) - start)
        m.slide(sid, start, start + n)
        start += n
        m.register_computed(sid, prompt, start)
        m.check_invariants()
    return cached


def _decode(m, sid, tokens, upto):
    for pos in range(len(tokens), upto):
        tokens = tokens + [7]
        m.grow(sid, pos + 2, token_ids=tokens, num_computed=pos, first_query=pos)
        m.check_invariants()
    return tokens


def test_a_lane_past_the_window_holds_the_window_and_one_block():
    m = _mgr()
    prompt = list(range(1, 101))
    _prefill(m, "s", prompt)
    tokens = _decode(m, "s", prompt, 150)
    held = m.held_blocks("s")
    assert held[0] == m.blocks_for(151)                  # global: every token
    assert all(h <= W // BS + 1 for h in held[1:])       # window + one block
    tables = m.block_tables("s")
    assert len({len(t) for t in tables}) == 1 and 0 not in tables[0]
    assert tables[1][0] == 0 and tables[1][-1] != 0      # released | still held
    assert m.stats().used_blocks == sum(held)            # what kv_util counts
    assert m.window_released == 3 * (held[0] - held[1])
    assert len(tokens) == 150


def test_published_sizes_a_lane_of_12288_tokens():
    # window 4,096, blocks of 64: the cell's own numbers, the map alone
    m = _mgr(num_blocks=2000, windows=(0, 4096, 4096, 4096), bs=64)
    prompt = [1 + i % 1000 for i in range(12288)]
    _prefill(m, "s", prompt, chunk=512)
    held = m.held_blocks("s")
    assert held[0] == 12289 // 64 + 1
    assert max(held[1:]) * 64 <= 4096 + 64
    _decode(m, "s", prompt, 12400)
    assert max(m.held_blocks("s")[1:]) * 64 <= 4096 + 64
    # admission asks the pool for what it will hold at rest, not 4 x 193
    assert m.blocks_needed(12289) == 193 + 3 * 65
    assert m.fits_ever(16384) and not _mgr(num_blocks=300, windows=(0, 4096), bs=64
                                            ).fits_ever(16384)


def test_released_blocks_are_reusable_at_once():
    m = _mgr(num_blocks=40, enable_prefix_caching=False)
    prompt = list(range(1, 121))
    m.allocate_cached("a", prompt, 121)                  # 16 global + 3 x 1
    assert m.stats().used_blocks == 19 and m.free_blocks == 20
    with pytest.raises(KVCacheExhausted):                # 16 + 3 x 5 at rest
        m.allocate("b", 121)
    m.slide("a", 0, 40)
    free_before = m.free_blocks
    released = m.slide("a", 80, 88)                      # the window moved on
    assert released > 0 and m.free_blocks > free_before - 3
    m.check_invariants()
    m.allocate("c", 8)                                   # takes freed blocks
    assert set(m.block_table("c")) <= set(range(1, 40))
    m.check_invariants()


def test_invariants_through_release_preemption_resume_and_free():
    m = _mgr(num_blocks=120)
    rng = random.Random(0)
    live = {}
    for step in range(300):
        op = rng.random()
        if op < 0.3 and len(live) < 5:
            sid = f"s{step}"
            prompt = [rng.randrange(1, 50) for _ in range(rng.randrange(3, 90))]
            try:
                _prefill(m, sid, prompt, chunk=rng.choice([4, 16, 40]))
                live[sid] = prompt
            except KVCacheExhausted:
                if sid in m._tables:
                    m.free(sid)                          # the scheduler's undo
        elif op < 0.8 and live:
            sid = rng.choice(sorted(live))
            try:
                live[sid] = _decode(m, sid, live[sid], len(live[sid]) + rng.randrange(1, 12))
            except KVCacheExhausted:
                m.free(sid)                              # preempted: recompute
                live.pop(sid)
        elif live:
            sid = rng.choice(sorted(live))
            m.free(sid)
            prompt = live.pop(sid)
            if rng.random() < 0.5:                       # resume: hits its own prefix
                try:
                    _prefill(m, sid + "r", prompt)
                    live[sid + "r"] = prompt
                except KVCacheExhausted:
                    if sid + "r" in m._tables:
                        m.free(sid + "r")
        m.check_invariants()
    for sid in list(live):
        m.free(sid)
    m.check_invariants()
    assert m.stats().used_blocks == 0 and m.free_blocks == 119
    with pytest.raises(KeyError):
        m.free("s0")


def test_a_prefix_hit_never_hands_out_a_block_whose_rows_were_lost():
    m = _mgr(num_blocks=100)
    prompt = list(range(1, 97))                          # 12 full blocks
    _prefill(m, "a", prompt)
    assert m.held_blocks("a")[1] < 12                    # the window slid
    # while every copy still stands (live or resting cached) the whole
    # prefix is a hit, in every group, and only the window's span is taken
    cached = _prefill(m, "b", prompt)
    assert cached == 88                                  # all but the last block
    assert m.block_tables("b")[0][:11] == m.block_tables("a")[0][:11]
    assert m.held_blocks("b")[1] <= W // BS + 1
    m.free("a"), m.free("b")
    m.check_invariants()
    # now other content reclaims some of the resting copies
    m.allocate("filler", 50 * BS)
    m.check_invariants()
    lost = {b for t in m.block_tables("filler") for b in t}
    cached = _prefill(m, "c", prompt)
    assert cached < 88
    hit = [b for t in m.block_tables("c") for b in t[: cached // BS] if b]
    assert not lost & set(hit)                           # none was overwritten
    # and an entry is either whole or gone: every group's copy or none
    for blocks in m._index.values():
        assert len(blocks) == 4 and all(m._hash_of[b] == m._hash_of[blocks[0]] for b in blocks)
    m.check_invariants()


def test_one_group_manager_is_what_it_was():
    m = KVBlockManager(16, 4)
    assert m.group_windows == (0,) and m.blocks_needed(9) == 3
    t = m.allocate("a", 9)
    assert m.block_tables("a") == [t] and m.slide("a", 5, 9) == 0
    assert m.grow("a", 13) == m.block_table("a") and len(m.block_table("a")) == 4
    with pytest.raises(ValueError, match="host"):
        KVBlockManager(16, 4, host_tier=object(), group_windows=(0, 8))


def test_scheduler_slides_chunks_preempts_and_shrinks_under_pressure():
    m = _mgr(num_blocks=30)
    s = Scheduler(m, max_num_seqs=4, max_step_tokens=40, prefill_chunk=24)
    a = Sequence("a", list(range(1, 91)), 4)
    s.add(a)
    s.add(Sequence("b", list(range(100, 180)), 4))
    steps = 0
    while s.has_work() and steps < 200:
        out = s.schedule()
        m.check_invariants()
        assert len(out.prefills) <= 1
        for c in out.prefills:
            # alone in a pool too small for window + chunk, the chunk shrinks
            assert c.num_tokens <= 24
            c.seq.num_computed = c.start + c.num_tokens
            m.register_computed(c.seq.request_id, c.seq.prompt, c.seq.num_computed)
            if c.last:
                c.seq.append_token(1)
        for q in out.decodes:
            q.append_token(1)
        for q in [q for q in s.running if q.should_stop() and q.is_decoding and q.output]:
            s.finish(q, "length")
        steps += 1
    assert not s.has_work() and m.stats().used_blocks == 0
    assert a.num_generated == 4 and m.window_released > 0
