"""KV-pool planner tests: CAM's planner vs pool replay."""
import numpy as np
import pytest

from repro.serve.kv_cache import BlockTrace, PagedKVPool
from repro.serve.planner import RequestMix, block_popularity, plan_kv_pool


# ---------------------------------------------------------------------------
# CAM-guided KV planner (paper Eq. 15 on the serving plane)
# ---------------------------------------------------------------------------

def _trace_for_mix(mix: RequestMix, block_tokens: int, seed=0):
    bt = BlockTrace(block_tokens)
    rng = np.random.default_rng(seed)
    schedule = []
    for step in range(mix.decode_steps):
        for rid in rng.permutation(mix.n_requests):
            schedule.append((int(rid), mix.shared_prefix, mix.mean_context))
    return bt.decode_trace(schedule)


#: Two decode mixes: long private contexts under a moderate shared prefix,
#: and a long shared system prompt ahead of short private turns.
MIXES = {
    "long-context": RequestMix(n_requests=16, shared_prefix=512,
                               mean_context=1024, decode_steps=12,
                               kv_bytes_per_token=1024),
    "long-prompt": RequestMix(n_requests=32, shared_prefix=2048,
                              mean_context=256, decode_steps=8,
                              kv_bytes_per_token=1024),
}


@pytest.mark.parametrize("mix_name", sorted(MIXES))
@pytest.mark.parametrize("block_tokens", [32, 64, 128])
def test_planner_hit_rate_matches_pool_replay(block_tokens, mix_name):
    """Round-robin decode gives a CYCLIC trace: the IRM (Che) estimate
    overestimates (paper §III-C's caveat transplanted to KV paging), while
    the structural closed form lands on the replay."""
    from repro.core import cache_models
    from repro.serve.planner import structural_hit_rate
    import jax.numpy as jnp

    mix = MIXES[mix_name]
    probs, refs_per_step = block_popularity(mix, block_tokens)
    n_distinct = probs.shape[0]
    pool_blocks = n_distinct // 3          # force real evictions
    est_irm = float(cache_models.hit_rate(
        "lru", pool_blocks, jnp.asarray(probs, jnp.float32),
        total_requests=refs_per_step * mix.n_requests * mix.decode_steps))
    est_struct = structural_hit_rate(mix, block_tokens, pool_blocks)
    pool = PagedKVPool(pool_blocks, block_tokens, 1024 * block_tokens)
    for ref in _trace_for_mix(mix, block_tokens):
        pool.reference(ref)
    assert abs(est_struct - pool.hit_rate) < 0.04, (est_struct, pool.hit_rate)
    assert abs(est_struct - pool.hit_rate) < abs(est_irm - pool.hit_rate)


def test_planner_picks_reasonable_block_size():
    mix = RequestMix(n_requests=64, shared_prefix=2048, mean_context=8192,
                     decode_steps=128, kv_bytes_per_token=4096)
    plan = plan_kv_pool(mix, hbm_budget_bytes=8 * 2**30,
                        weight_bytes=4 * 2**30)
    assert plan.block_tokens in plan.candidates
    # the chosen block size must be the argmin of its own candidate table
    assert plan.candidates[plan.block_tokens] == min(plan.candidates.values())


def test_planner_cost_decreases_with_budget():
    mix = RequestMix(n_requests=32, shared_prefix=1024, mean_context=4096,
                     decode_steps=64, kv_bytes_per_token=2048)
    costs = []
    for budget in (2, 4, 8):
        plan = plan_kv_pool(mix, hbm_budget_bytes=budget * 2**30,
                            weight_bytes=1 * 2**30)
        costs.append(plan.transfer_bytes_per_step)
    assert costs[0] >= costs[1] >= costs[2]
