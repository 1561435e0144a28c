"""The order in which a retiring sequence's blocks enter the prefix cache's
LRU, and what eviction then takes first.

``serve.kanana2_30b.doc_turns`` asks the same 32 long documents round and
round; its steadiness rests on this order. A retiring request gives back its
document's shared blocks first (in chain order) and registers its own tail
(the answer's blocks) after them; eviction takes the oldest entry. A document
asked again re-enters at the young end every time it is released, so what
eviction meets first are the tails of requests that retired before the
document's last release, which nothing asks for again; the document's own
blocks come up only once every older tail is gone."""

from dlti_tpu.serving.block_manager import BlockManager
from dlti_tpu.serving.prefix_cache import PrefixCachingAllocator

BS = 4
DOC = list(range(100, 112))          # three whole blocks


def _ask(pc, answer):
    """One request over DOC: match, acquire, allocate the rest, retire with
    ``answer`` appended. Returns (document blocks, own blocks)."""
    tokens = DOC + [7]                # the prompt ends past the document
    shared, n = pc.match_prefix(tokens)
    pc.acquire(shared)
    total = tokens + answer
    own = pc.allocate(-(-(len(total) + 1) // BS) - len(shared))
    pc.release_sequence(total, shared + own)
    return shared, own


def test_shared_blocks_enter_the_lru_before_the_requests_own_tail():
    pc = PrefixCachingAllocator(BlockManager(num_blocks=32, block_size=BS))
    first, first_own = _ask(pc, [1, 2, 3, 4, 5, 6, 7])     # cold: all its own
    assert first == [] and pc.num_cached_blocks == 5
    doc = list(pc._lru)[:3]
    shared, own = _ask(pc, [9, 8, 7, 6, 5, 4, 3])          # a hit on DOC
    assert shared == doc
    order = list(pc._lru)
    # the first request's tail is the oldest now; then the document, released
    # again a moment ago; then the second request's tail, youngest
    assert order[:2] == first_own[3:5]
    assert order[2:5] == doc
    assert set(order[5:]) <= set(own) and len(order) == 7


def test_eviction_takes_old_tails_before_a_document_asked_since():
    pc = PrefixCachingAllocator(BlockManager(num_blocks=16, block_size=BS))
    _ask(pc, [1, 2, 3, 4, 5, 6, 7])
    doc = list(pc._lru)[:3]
    for i in range(4):                # asked again and again: tails pile up
        _ask(pc, [20 + i] * 7)
        assert pc.match_prefix(DOC + [7])[0] == doc
    # 15 blocks: 3 of the document, at most 12 of tails; the pool ran dry on
    # the way and gave up tails, never a block of the document
    assert pc.num_free + pc.num_reclaimable == 15
    assert all(b in pc._by_block for b in doc)
    # ... until nothing older is left: the document then goes head first
    taken = pc.allocate(pc.num_free + pc.num_reclaimable - 2)
    assert taken is not None
    assert pc.match_prefix(DOC + [7])[1] < len(DOC)


# -- chain keys that keep their hash, and blocks registered while running -----

def _plain_keys(tokens, bs):
    keys, prev = [], ()
    for i in range(len(tokens) // bs):
        prev = (prev, tuple(tokens[i * bs:(i + 1) * bs]))
        keys.append(prev)
    return keys


def test_chain_keys_are_the_nested_tuples_they_were():
    """Content, hash and repr of a key are a plain nested tuple's (the tiers
    name a disk block by the repr), so nothing that holds keys sees the
    difference; only the hash is computed once."""
    tokens = list(range(40))
    keys = PrefixCachingAllocator._chain_keys(tokens, BS)
    plain = _plain_keys(tokens, BS)
    assert keys == plain and len(keys) == 10
    assert [hash(k) for k in keys] == [hash(k) for k in plain]
    assert [repr(k) for k in keys] == [repr(k) for k in plain]
    assert {plain[3]: 1}[keys[3]] == 1 and {keys[3]: 1}[plain[3]] == 1


def test_a_match_walks_on_from_the_registered_key_objects():
    """The walk's lookups end at an identity: key i of a second match holds
    the registered key i-1 itself, not an equal copy whose comparison would
    run down the whole chain again (O(n^2) over a long document)."""
    pc = PrefixCachingAllocator(BlockManager(num_blocks=64, block_size=BS))
    tokens = list(range(200, 240))
    pc.release_sequence(tokens, pc.allocate(10))
    walked = list(pc._walk(tokens))
    assert all(entry is not None for _key, entry in walked)
    for (key, entry), (parent, _e) in zip(walked[1:], walked):
        assert key is entry.key and key[0] is parent
    assert pc.match_prefix(tokens + [0])[1] == 40


def test_register_makes_a_running_sequences_blocks_matchable_and_pinned():
    pc = PrefixCachingAllocator(BlockManager(num_blocks=16, block_size=BS))
    own = pc.allocate(4)                             # DOC, and a tail begun
    assert pc.register(DOC, own[:3]) == own[:3]
    assert pc.match_prefix(DOC + [7]) == (own[:3], 12)
    # pinned for the running sequence: eviction cannot take them
    assert pc.num_reclaimable == 0
    assert pc.allocate(pc.num_free) is not None and pc.allocate(1) is None
    # a second register of the same blocks (they are shared now) changes nothing
    assert pc.register(DOC, own[:3]) == own[:3]
    assert [pc._by_block[b].refcount for b in own[:3]] == [1, 1, 1]
    pc.release_sequence(DOC + [7, 1, 2, 3], own)
    assert [pc._by_block[b].refcount for b in own] == [0, 0, 0, 0]
    assert pc.num_reclaimable == 4


def test_register_gives_up_a_copy_another_sequence_registered_first():
    """Two cold prefills of one document at once: the second to finish frees
    its copies and goes on over the first's blocks, one reference each."""
    pc = PrefixCachingAllocator(BlockManager(num_blocks=16, block_size=BS))
    a, b = pc.allocate(3), pc.allocate(3)
    free = pc.num_free
    assert pc.register(DOC, a) == a
    assert pc.register(DOC[:8] + [1, 2, 3, 4], b) == a[:2] + b[2:]
    assert pc.num_free == free + 2
    assert [pc._by_block[x].refcount for x in a + b[2:]] == [2, 2, 1, 1]
    pc.release_sequence(DOC, a)
    pc.release_sequence(DOC[:8] + [1, 2, 3, 4], a[:2] + b[2:])
    assert all(e.refcount == 0 for e in pc._by_block.values())
    assert pc.num_free + pc.num_reclaimable == 15
