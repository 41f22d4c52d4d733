"""Shared cases for the paged decode kernel's walk (interpret mode): the
bf16/fp32 kernel (test_serving_paged.py) and its int8 twin
(test_serving_quant.py) run the same lengths against their oracles.

The walk copies ``P`` pages to a chunk into one of two chunk buffers;
with the tiny shapes here ``P`` would be the whole table, so the cases
set ``_CHUNK_ROWS`` to two pages' rows (P = 2: a 16-token chunk at block
size 8) and name every edge of the loop by its length."""
import jax.numpy as jnp
import numpy as np

BS, KVH, D, NB = 8, 2, 16, 24
P = 2                                        # pages per chunk in these cases

# name -> (table width, per-slot lengths, query heads per kv head,
#          slots whose table row is zeroed: dead slots)
WALK_CASES = {
    # the case the kernel has been pinned with since it was written
    "ragged_three_slots": (4, [5, 17, 32], 2, ()),
    "one_token": (5, [1, 1], 2, ()),
    "exactly_one_block": (5, [BS, 3], 2, ()),
    "one_token_into_next_block": (5, [BS + 1], 2, ()),
    "exactly_one_chunk": (5, [P * BS], 2, ()),
    # the third page goes to the other buffer: the double buffer's
    # hand-over, inside a slot and on to the next slot's first chunk
    "one_page_past_a_chunk": (5, [P * BS + 1, (P + 1) * BS, 2], 2, ()),
    # 5 is not a multiple of P: the last chunk is half live
    "full_table_width_not_multiple_of_chunk": (5, [5 * BS, 5 * BS], 2, ()),
    "length_beyond_table_is_clamped": (3, [3 * BS + 11, 7], 2, ()),
    "all_edges_mixed_with_dead_slot":
        (5, [1, BS, BS + 1, P * BS, 1, P * BS + 1, 5 * BS, 29], 2, (4,)),
    "gqa_groups_1": (5, [1, 17, 40, 1, 24], 1, (3,)),
    "gqa_groups_4": (5, [1, 17, 40, 1, 24], 4, (3,)),
    "gqa_groups_8": (5, [1, 17, 40, 1, 24], 8, (3,)),
}


def chunk_rows():
    """What ``_CHUNK_ROWS`` has to be for P pages a chunk here."""
    return P * BS * KVH


def walk_inputs(case, seed=0):
    """q (S, H, D), fp32 K and V arenas, the block table and lengths."""
    mb, lens, g, dead = WALK_CASES[case]
    rs = np.random.RandomState(seed)
    s = len(lens)
    q = jnp.asarray(rs.randn(s, KVH * g, D).astype(np.float32))
    ka = jnp.asarray(3 * rs.randn(NB, BS, KVH, D).astype(np.float32))
    va = jnp.asarray(rs.randn(NB, BS, KVH, D).astype(np.float32))
    tbl = rs.randint(1, NB, (s, mb)).astype(np.int32)
    for i in dead:
        tbl[i] = 0
    return q, ka, va, jnp.asarray(tbl), jnp.asarray(lens, jnp.int32)
