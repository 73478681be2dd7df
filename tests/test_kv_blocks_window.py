"""The window group of ``serving/kv_blocks.py``: the pages of a patterned
model's sliding-window layers, beside the full group's (plain host
bookkeeping, no device)."""

import numpy as np
import pytest

from megatron_llm_tpu.ops.paged_kv import window_pages_bound
from megatron_llm_tpu.serving.kv_blocks import (BlockManager, NoCapacity,
                                                WindowGroup)

BS, WINDOW, CHUNK = 16, 1024, 512
BOUND = window_pages_bound(WINDOW, CHUNK, BS)


def _manager(slots=4, blocks=4000, window_blocks=None, max_blocks=2112,
             window=WINDOW, chunk=CHUNK, bs=BS):
    bound = window_pages_bound(window, chunk, bs)
    group = WindowGroup(window_blocks or slots * bound + 1, bs, slots,
                        max_blocks, window, bound)
    return BlockManager(blocks, bs, slots, max_blocks, window=group)


def _run(m, slot, total, chunk=CHUNK, prompt=None):
    """A request's launches: chunks of the prompt, then one token a step.
    Yields (the launch's first position, the next launch's) after each
    ``window_advance``."""
    prompt = total if prompt is None else prompt
    pos = 0
    while pos < total:
        n = min(chunk, prompt - pos) if pos < prompt else 1
        m.window_advance([(slot, pos, n)])
        pos += n
        yield pos - n, pos


def test_the_bound_is_the_window_a_chunk_and_a_page():
    assert BOUND == 97 == (1024 + 512) // 16 + 1
    assert window_pages_bound(16, 16, 8) == 5
    assert window_pages_bound(4096, 64, 16) == 261


@pytest.mark.parametrize("total,prompt", [(33_000, 32_768), (700, 600),
                                          (1537, 1537), (5000, 17)])
def test_a_requests_window_pages_never_pass_the_bound(total, prompt):
    """However long it grows, prefill in chunks then decode: at most 97
    pages of 16 at 1,024 / 512 / 16, and a short request its own pages."""
    m = _manager()
    slot = m.alloc(total)
    worst = 0
    for start, pos in _run(m, slot, total, prompt=prompt):
        held = m.stats()["window_blocks_in_use"]
        worst = max(worst, held)
        # every key the launch's queries can see is on a held page, and
        # nothing behind the window of its first is
        first = max(start - WINDOW + 1, 0) // BS
        row = m.window.tables[slot]
        assert (row[first:-(-pos // BS)] > 0).all()
        assert (row[:first] == 0).all() and (row[-(-pos // BS):] == 0).all()
    m.check_invariants()
    assert worst <= min(BOUND, -(-total // BS))
    if prompt > WINDOW + CHUNK + 2 * BS:
        assert worst >= BOUND - 1
    s = m.stats()
    assert s["window_pages_spanned"] == -(-total // BS)
    assert s["window_pages_returned"] == s["window_pages_spanned"] - s[
        "window_blocks_in_use"]
    assert s["blocks_in_use"] == -(-total // BS)     # the full group: all
    m.free(slot)
    m.check_invariants()
    assert m.stats()["window_blocks_in_use"] == 0
    assert m.stats()["window_blocks_free"] == m.window.num_blocks - 1


def test_pages_returned_are_handed_out_again():
    """A window group of ONE request's bound serves a context of many
    times that: the pages a request gives back are the pages it takes."""
    m = _manager(slots=1)
    assert m.window.num_blocks - 1 == BOUND
    slot = m.alloc(30_000)
    seen = set()
    for _ in _run(m, slot, 30_000):
        seen |= set(m.window.tables[slot][m.window.tables[slot] > 0].tolist())
    assert len(seen) <= BOUND and m.stats()["window_pages_spanned"] == 1875
    # and another request's: two slots over a group of two bounds
    m = _manager(slots=2)
    a, b = m.alloc(9000), m.alloc(9000)
    for _ in zip(_run(m, a, 9000), _run(m, b, 9000)):
        m.check_invariants()
    assert m.stats()["window_blocks_in_use"] <= 2 * BOUND


def test_admission_refuses_when_either_group_is_short():
    # the window group short: room for one request's bound, not two
    m = _manager(slots=4, window_blocks=BOUND + 50 + 1)
    first = m.alloc(20_000)
    assert m.can_admit(50 * BS)             # a short one reserves 50 pages
    assert not m.can_admit(51 * BS)
    with pytest.raises(NoCapacity, match="window group"):
        m.alloc(20_000)
    m.check_invariants()
    # what is reserved is kept though the request holds little of it yet
    m.window_advance([(first, 0, CHUNK)])
    assert m.stats()["window_blocks_in_use"] == CHUNK // BS
    assert not m.can_admit(51 * BS)
    small = m.alloc(50 * BS)
    assert not m.can_admit(BS)
    m.free(first)
    assert m.can_admit(20_000)
    m.free(small)
    m.check_invariants()
    # the full group short: the window group has room
    m = _manager(slots=4, blocks=1000)
    m.alloc(999 * BS // 2)
    assert not m.can_admit(999 * BS)
    with pytest.raises(NoCapacity, match="free/evictable blocks"):
        m.alloc(999 * BS)
    assert m.can_admit(400 * BS)
    m.check_invariants()


def test_check_invariants_after_every_step_of_a_random_schedule():
    rng = np.random.default_rng(0)
    m = _manager(slots=3, blocks=600, window=64, chunk=32, bs=8,
                 max_blocks=200)
    bound = m.window.bound
    live = {}
    for _ in range(400):
        if live and rng.random() < 0.15:
            slot = int(rng.choice(list(live)))
            m.free(slot)
            del live[slot]
        elif len(live) < 3 and rng.random() < 0.3:
            total = int(rng.integers(5, 1500))
            if m.can_admit(total):
                slot = m.alloc(total)
                live[slot] = _run(m, slot, total, chunk=32,
                                  prompt=int(rng.integers(1, total + 1)))
        elif live:
            slot = int(rng.choice(list(live)))
            if next(live[slot], None) is None:
                m.free(slot)
                del live[slot]
        m.check_invariants()
        assert all(len(h) <= bound for h in m.window._held.values())
    assert m.stats()["window_pages_returned"] > 100


def test_check_invariants_finds_a_page_in_two_places():
    m = _manager(slots=2)
    a, b = m.alloc(4000), m.alloc(4000)
    m.window_advance([(a, 0, CHUNK)])
    m.window_advance([(b, 0, CHUNK)])
    page = m.window._held[a][0]
    m.window._free.append(page)
    with pytest.raises(AssertionError, match="free and held|free twice"):
        m.check_invariants()
    m.window._free.pop()
    m.window._held[b][99] = page
    with pytest.raises(AssertionError):
        m.check_invariants()


def test_a_window_group_adopts_no_prefix():
    """The prefix rule that is not built is a refusal: a manager with a
    window group takes no prefix cache and no host tier."""
    group = WindowGroup(200, BS, 2, 2112, WINDOW, BOUND)
    with pytest.raises(ValueError, match="adopts no prefix"):
        BlockManager(4000, BS, 2, 2112, prefix_cache=True, window=group)
    m = BlockManager(4000, BS, 2, 2112, window=group)
    prompt = list(range(1, 400))
    m.free(m.alloc(500, prompt), token_ids=prompt, n_written=399)
    slot = m.alloc(500, prompt)
    assert m.slot_cached_tokens(slot) == 0
    m.check_invariants()
