"""Reproduction of "The CORE Storage Primitive" (cs.DC 2013), grown into
a jax_pallas storage + serving system.

Package map:

  coding/    GF(2^8) arithmetic, generic linear codes, RS / LRC / SPC.
  core/      the (n, k, t) CORE product code: codec, failure matrices,
             recoverability, repair scheduling (row/column/RGS).
  kernels/   Pallas TPU kernels for the compute hot spots — bit-sliced
             GF(256) coefficient x data matmul and vertical XOR parity,
             single-stripe and as the ragged descriptor-tile megakernel
             the gateway decodes and encodes with, plus a pure-jnp
             oracle (ref.py) and backend auto-detect (backend.py).
  storage/   the simulated cluster: anti-colocated BlockStore, the
             priority-class NetSimulator fabric, and BlockFixer (repair
             engine: hdfs_raid / hdfs_raid_opt / core modes).
  gateway/   the client-facing serving layer: Zipf/Poisson workloads,
             per-request degraded-read planning (paper Table 1 costs),
             ragged megakernel decode coalescing, a rebuild-cost-aware
             block cache, and an event-driven PUT/GET gateway where
             background repair contends with foreground reads on the
             shared fabric (examples/gateway_serving.py is the
             quickstart).
  checkpoint/ CORE-coded training checkpoints over the block store.
  models/, train/, serve/, launch/, configs/, data/, analysis/
             the jax model stack the storage layer feeds (training and
             serving loops, meshes, HLO cost/roofline analysis).

The chip benchmark (benchmarks/chip/, cells in BENCHMARK.json) measures
degraded reads and node repair on a TPU; benchmarks/run.py prints the
paper's analytical figures; tests/ cross-validate every layer against
analytic counts or pure-numpy oracles.
"""
