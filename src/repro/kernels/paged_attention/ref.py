"""Pure-jnp oracle: gather one layer's view through the block tables, then
exact float32 softmax attention of each query head over its KV head's
positions [0, length)."""
import jax
import jax.numpy as jnp
import numpy as np


def paged_attention(q, k_pool, v_pool, layer, tables, lengths):
    """q: [B, H, dh]; pools: [L, P, block, kvh, dh]; tables: [B, NB];
    lengths: [B]. Returns [B, H, dh] in q's dtype."""
    b, h, dh = q.shape
    kvh = k_pool.shape[3]
    k = jnp.take(k_pool[layer], tables, axis=0).reshape(b, -1, kvh, dh)
    v = jnp.take(v_pool[layer], tables, axis=0).reshape(b, -1, kvh, dh)
    qg = q.astype(jnp.float32).reshape(b, kvh, h // kvh, dh)
    s = jnp.einsum("bgjd,btgd->bgjt", qg, k.astype(jnp.float32)) / np.sqrt(dh)
    live = jnp.arange(k.shape[1])[None, :] < lengths[:, None]      # [B, T]
    s = jnp.where(live[:, None, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bgjt,btgd->bgjd", p, v.astype(jnp.float32))
    return o.reshape(b, h, dh).astype(q.dtype)
