# Continuous batching through the sandbox: requests of different lengths
# share one decode batch and one paged KV pool (models/serving.py over
# ops/paged_kv_cache.py). Three prompts are admitted as rows free up; each
# result must equal that prompt's solo greedy decode — batching other
# requests alongside cannot change an answer.
#
# f32 so the equality assert is trustworthy (same reasoning as
# speculative-decode.py: bf16 near-tie argmax flips are rounding noise) —
# and at "highest" matmul precision, because the assert compares two
# DIFFERENT programs (generate_cached vs the paged batcher) and a TPU runs
# f32 matmuls at reduced precision by default.
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from bee_code_interpreter_tpu.models import transformer as T
from bee_code_interpreter_tpu.models.serving import ContinuousBatcher

jax.config.update("jax_default_matmul_precision", "highest")

# The size follows the device the code finds — and says so: a run that
# landed on the host CPU must never read as a run on the chip.
device = jax.devices()[0]
on_tpu = device.platform == "tpu"
config = dataclasses.replace(
    T.TransformerConfig(
        vocab_size=32000, d_model=1024, n_layers=8, n_heads=16,
        n_kv_heads=4, max_seq_len=2048,
    ) if on_tpu else T.TransformerConfig.tiny(),
    dtype=jnp.float32,
)
print(f"platform={device.platform} device_kind={device.device_kind!r} "
      f"devices={len(jax.devices())} model=d{config.d_model}x"
      f"{config.n_layers}L ({'1024-wide' if on_tpu else 'tiny: no TPU found'})")
params = T.init_params(config, jax.random.PRNGKey(0))
model = T.Transformer(config)

lengths = [5, 11, 8]
new_tokens = 12
prompts = [
    np.asarray(jax.random.randint(jax.random.PRNGKey(i + 1), (L,), 0,
                                  config.vocab_size))
    for i, L in enumerate(lengths)
]
solo = [
    np.asarray(model.generate_cached(
        params, jnp.asarray(p)[None, :], max_new_tokens=new_tokens
    )[0, len(p):]).tolist()
    for p in prompts
]

batcher = ContinuousBatcher(
    params, config, max_batch=2, n_pages=32, page_size=8,
    max_pages_per_seq=4,
)
t0 = time.time()
pending = list(enumerate(prompts))
requests: dict[int, int] = {}
steps = 0
while pending or any(not batcher.is_done(r) for r in requests.values()):
    while pending and batcher.has_free_row():
        idx, prompt = pending[0]
        try:
            requests[idx] = batcher.submit(prompt, new_tokens)
        except RuntimeError:
            break  # pages exhausted: decode until some free
        pending.pop(0)
    batcher.step()
    steps += 1

for idx in range(len(prompts)):
    got = batcher.result(requests[idx])
    assert got == solo[idx], (idx, got, solo[idx])
print(f"continuous batching OK: {len(prompts)} requests over max_batch=2, "
      f"{steps} steps, {time.time() - t0:.1f}s, outputs == solo decode")

# --- speculative mode: a small draft proposes, each row commits its OWN
# accept length per round (no lockstep minimum across the batch) — output
# still exactly equals the solo greedy decode.
draft_config = dataclasses.replace(
    config, n_layers=1, d_model=32, n_heads=2, n_kv_heads=2, d_ff=64,
)
draft_params = T.init_params(draft_config, jax.random.PRNGKey(9))
spec = ContinuousBatcher(
    params, config, max_batch=2, n_pages=32, page_size=8,
    max_pages_per_seq=4, draft_params=draft_params,
    draft_config=draft_config, gamma=3,
)
reqs = [spec.submit(p, new_tokens) for p in prompts[:2]]
rounds = 0
while not all(spec.is_done(r) for r in reqs):
    spec.step()
    rounds += 1
for i, r in enumerate(reqs):
    assert spec.result(r) == solo[i], (i, spec.result(r), solo[i])
print(f"speculative serving OK: {len(reqs)} requests, {rounds} rounds for "
      f"{new_tokens} tokens each (gamma=3), outputs == solo decode")

# --- prefix caching: a repeat prompt hits the page index and admits via a
# suffix-only prefill — shared pages are reused (refcounted, kept past
# retirement), and the greedy output is exactly the solo decode still.
pc = ContinuousBatcher(
    params, config, max_batch=2, n_pages=32, page_size=8,
    max_pages_per_seq=4, prefix_cache=True,
)
r1 = pc.submit(prompts[1], new_tokens)
pc.run_to_completion()
r2 = pc.submit(prompts[1], new_tokens)
pc.run_to_completion()
assert pc.result(r1) == pc.result(r2) == solo[1]
s = pc.prefix_stats
print(f"prefix caching OK: repeat prompt hits={s['hits']} pages_reused="
      f"{s['pages_reused']}, outputs == solo decode")

# --- dp × tp serving: two engine replicas, each tensor-parallel over its
# own pair of devices, behind one router — the standard serving topology,
# exercised right here on the virtual device mesh.
import jax
import numpy as np
from jax.sharding import Mesh

from bee_code_interpreter_tpu.models.replicated import ReplicatedEngine

if len(jax.devices()) >= 4:
    meshes = [
        Mesh(np.array(jax.devices()[0:2]), ("tp",)),
        Mesh(np.array(jax.devices()[2:4]), ("tp",)),
    ]
    rep = ReplicatedEngine.build(
        params, config, 2, meshes=meshes,
        max_batch=2, n_pages=32, page_size=8, max_pages_per_seq=4,
    )
    rtix = [rep.submit(p, new_tokens) for p in prompts]
    rep.run_to_completion()
    for i, t in enumerate(rtix):
        assert rep.result(t) == solo[i], (i, rep.result(t), solo[i])
    replicas_used = {rep.replica_of(t) for t in rtix}
    print(f"dp x tp serving OK: {len(rtix)} requests over 2 replicas x tp=2 "
          f"(replicas used: {sorted(replicas_used)}), outputs == solo decode")
else:
    print("dp x tp serving SKIPPED: needs >= 4 devices")
