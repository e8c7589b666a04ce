"""Pure-jnp oracle: every row's k experts gathered and run one by one."""
import jax
import jax.numpy as jnp


def routed_experts_ref(x: jax.Array, top_i: jax.Array, top_p: jax.Array,
                       w_gate: jax.Array, w_up: jax.Array, w_down: jax.Array,
                       *, lo: int = 0) -> jax.Array:
    n_held = w_gate.shape[0]
    local = top_i - lo
    held = (local >= 0) & (local < n_held)
    e = jnp.clip(local, 0, n_held - 1)                      # (R, k)
    a = jnp.einsum("rd,rkdf->rkf", x, w_gate[e],
                   preferred_element_type=jnp.float32)
    b = jnp.einsum("rd,rkdf->rkf", x, w_up[e],
                   preferred_element_type=jnp.float32)
    h = (jax.nn.silu(a) * b).astype(x.dtype)
    y = jnp.einsum("rkf,rkfd->rkd", h, w_down[e],
                   preferred_element_type=jnp.float32)
    w = jnp.where(held, top_p, 0.0).astype(jnp.float32)
    return jnp.einsum("rk,rkd->rd", w, y)
