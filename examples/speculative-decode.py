# Speculative decoding through the sandbox: a 1-layer draft proposes, the
# target verifies a whole window per forward — output is EXACTLY the
# target's greedy decode (the draft only changes how many target forwards
# run). Uses the bundled models/speculative.py.
#
# f32 everywhere, at "highest" matmul precision: the equality check compares
# the window forward against single-step decode, whose logits agree only up
# to rounding — at bf16 (or a TPU's default reduced-precision f32 matmul) a
# near-tied argmax can flip, which is rounding noise, not a speculation bug.
# f32 margins dwarf that rounding, making the assert trustworthy.
import dataclasses
import time

import jax

from bee_code_interpreter_tpu.models import transformer as T
from bee_code_interpreter_tpu.models import speculative_generate

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
    dtype=jax.numpy.float32,
)
print(f"platform={device.platform} device_kind={device.device_kind!r} "
      f"model=d{config.d_model}x{config.n_layers}L "
      f"({'1024-wide' if on_tpu else 'tiny: no TPU found'})")
draft_config = dataclasses.replace(config, n_layers=1)

params = T.init_params(config, jax.random.PRNGKey(0))
draft_params = T.init_params(draft_config, jax.random.PRNGKey(1))
prompt = jax.random.randint(jax.random.PRNGKey(2), (1, 16), 0, config.vocab_size)
n_new = 32 if on_tpu else 8

def run():
    return speculative_generate(
        params, config, draft_params, draft_config, prompt,
        max_new_tokens=n_new, gamma=4,
    )

spec = run()  # warm: trace + compile happens here, not in the timed call
jax.block_until_ready(spec)
t0 = time.time()
spec = run()
jax.block_until_ready(spec)
spec_s = time.time() - t0

greedy = T.Transformer(config).generate_cached(params, prompt, max_new_tokens=n_new)
exact = bool((spec == greedy).all())
print(f"speculative decode: {n_new} tokens in {spec_s:.2f}s, "
      f"exact-vs-greedy {exact}")
assert exact
