"""Microbenchmarks of the Pallas kernel ref paths + packing arithmetic:
bitserial HBM-byte reduction (the serving payoff) and kernel-vs-ref
timing on CPU (interpret mode timing is NOT a TPU number — the derived
column carries the byte ratios that ARE hardware-invariant)."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.core.packing import pack_from_float
from repro.kernels import ops

from .common import emit, time_call


def main():
    K, N, M = 2048, 2048, 64
    w = jax.random.normal(jax.random.PRNGKey(0), (K, N)) * 0.1
    x = jax.random.normal(jax.random.PRNGKey(1), (M, K), jnp.float32)
    bf16_bytes = K * N * 2
    for n_bits in (2, 4, 8):
        pw = pack_from_float(w, n_bits)
        us, _ = time_call(lambda: ops.bitserial_matmul(x, pw, use_pallas=False))
        emit(
            f"kernels/bitserial_{n_bits}b", us,
            f"hbm_bytes={pw.hbm_bytes()};bf16_bytes={bf16_bytes};"
            f"byte_ratio={pw.hbm_bytes()/bf16_bytes:.3f}",
        )
    # Per-group scale rows (the exact-export epilogue): same packed bytes
    # plus a G-float row; the epilogue multiply should be timing-neutral
    # vs the per-tensor scale.
    for groups in (16, N):
        pwg = pack_from_float(w, 4, group_cols=groups)
        us, _ = time_call(lambda: ops.bitserial_matmul(x, pwg, use_pallas=False))
        emit(
            f"kernels/bitserial_4b_g{groups}", us,
            f"hbm_bytes={pwg.hbm_bytes()};scale_row={pwg.scale.size}",
        )
    us, _ = time_call(lambda: x @ w)
    emit("kernels/dense_matmul_f32", us, f"hbm_bytes={K*N*4}")

    q = jax.random.normal(jax.random.PRNGKey(2), (8, 1024, 64), jnp.float32)
    us, _ = time_call(lambda: ops.flash_attention(q, q, q, causal=True, use_pallas=False))
    emit("kernels/flash_attention_ref", us, "oracle_path")

    paged_attention_sweep()

    planes = jax.random.normal(jax.random.PRNGKey(3), (16, 65536))
    us, _ = time_call(lambda: ops.bgl_sumsq(planes, use_pallas=False))
    emit("kernels/bgl_sumsq_ref", us, "oracle_path")


def paged_attention_sweep():
    """Paged decode attention, live-length vs pool-size sweep: the
    block-table-walking kernel reads only each lane's live blocks, the
    jnp gather path materialises every lane's full table view — so
    kernel HBM bytes scale with occupancy while gather bytes are flat at
    pool capacity.  Byte columns are analytic (hardware-invariant);
    interpret-mode timings are NOT TPU numbers."""
    B, KV, G, d, bs, nb_lane = 4, 2, 2, 16, 8, 16
    n_blocks = B * nb_lane  # pool exactly covers the lanes' tables
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(B, KV, G, d)), jnp.float32)
    k_pool = jnp.asarray(rng.normal(size=(n_blocks, KV, bs, d)), jnp.float32)
    v_pool = jnp.asarray(rng.normal(size=(n_blocks, KV, bs, d)), jnp.float32)
    table = jnp.asarray(rng.permutation(n_blocks).reshape(B, nb_lane), jnp.int32)
    row_bytes = KV * d * 4 * 2  # one K row + one V row, f32
    gather_bytes = B * nb_lane * bs * row_bytes  # flat: full pool view per lane
    for frac in (0.25, 0.5, 1.0):
        live_rows = max(1, int(frac * nb_lane * bs))
        # stagger lanes around the target occupancy (lane 0 the longest)
        pos = jnp.asarray([max(0, live_rows - 1 - i * bs // 2) for i in range(B)],
                          jnp.int32)
        live_blocks = int(np.sum(np.asarray(pos) // bs + 1))
        kernel_bytes = live_blocks * bs * row_bytes
        us_k, _ = time_call(lambda: ops.paged_attention(
            q, k_pool, v_pool, table, pos, use_pallas=True))
        us_g, _ = time_call(lambda: ops.paged_attention(
            q, k_pool, v_pool, table, pos, use_pallas=False))
        ratio = gather_bytes / kernel_bytes
        emit(
            f"kernels/paged_attention_live{int(frac * 100)}", us_k,
            f"gather_us={us_g:.1f};kernel_bytes={kernel_bytes};"
            f"gather_bytes={gather_bytes};byte_ratio={ratio:.2f};"
            f"toks_per_s={B / (us_k * 1e-6):.0f}",
        )
        if frac <= 0.5:
            # the tentpole's point: at half occupancy the kernel must read
            # at least 2x fewer KV bytes than the full-pool gather
            assert ratio >= 2.0, (frac, kernel_bytes, gather_bytes)


if __name__ == "__main__":
    main()
