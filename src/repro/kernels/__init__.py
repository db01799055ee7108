# Pallas TPU kernels for the compute hot-spots (flash_attention,
# paged_attention, lstm_cell, lars, mamba, moe_gmm) + ops.py
# (backend-dispatching wrappers) + paged_pool.py (the paged KV pool's
# layout and its device operations) + ref.py (pure-jnp oracles used by
# the allclose sweeps).
