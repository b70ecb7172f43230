"""JUNO-attention in an LM decode loop (beyond-paper, paper §6.5 direction).

Prefill a small LM, PQ-index its KV cache, then decode comparing exact
attention vs JUNO top-C attention: agreement of attended outputs, and the
memory-traffic model that makes it a win on memory-bound decode.

    PYTHONPATH=src python examples/juno_attention_lm.py
"""
import jax
import jax.numpy as jnp

from repro.configs import get_smoke_config
from repro.models import get_model
from repro.models.juno_attention import (build_kv_index,
                                         juno_decode_attention,
                                         traffic_model)
from repro.models.layers import attention
from repro.models.params import init_params


def main():
    cfg = get_smoke_config("phi4_mini_3_8b")
    model = get_model(cfg)
    params = init_params(model.schema, jax.random.PRNGKey(0))

    # prefill 96 tokens
    s_max, prompt_len, b = 128, 96, 2
    tokens = jax.random.randint(jax.random.PRNGKey(1), (b, prompt_len), 0,
                                cfg.vocab_size).astype(jnp.int32)
    cache = init_params(model.cache_schema(b, s_max), jax.random.PRNGKey(2))
    _, cache = model.prefill(params, {"tokens": tokens}, cache)

    # take layer 0's cache and a random query; compare attention outputs
    k_cache = cache["blocks"]["k"][0]      # (B, S, KVH, hd)
    v_cache = cache["blocks"]["v"][0]
    pos = jnp.full((b,), prompt_len, jnp.int32)
    q = jax.random.normal(jax.random.PRNGKey(3),
                          (b, 1, cfg.n_heads, cfg.head_dim),
                          k_cache.dtype) * 0.5

    exact = attention(q, k_cache, v_cache, causal=True,
                      q_offset=pos, kv_len=pos + 1, chunk=64)

    index = build_kv_index(k_cache, n_entries=16)
    for top_c in [8, 24, 64, 96]:
        approx = juno_decode_attention(q, index, k_cache, v_cache, pos,
                                       top_c=top_c)
        err = float(jnp.linalg.norm(approx - exact)
                    / jnp.linalg.norm(exact))
        cos = float(jnp.sum(approx * exact)
                    / (jnp.linalg.norm(approx) * jnp.linalg.norm(exact)))
        print(f"top_c={top_c:4d}  rel_err={err:.3f}  cosine={cos:.4f}")

    print("\nmemory-traffic model at production scale (decode_32k, hd=128):")
    for top_c in [256, 512, 1024]:
        t = traffic_model(32_768, 128, top_c)
        print(f"  top_c={top_c:5d}: exact={t['exact_bytes'] / 1e6:.1f}MB/head"
              f"  juno={t['juno_bytes'] / 1e6:.2f}MB/head"
              f"  -> {t['reduction_x']:.1f}x less HBM traffic")


if __name__ == "__main__":
    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    main()
