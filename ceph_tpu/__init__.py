"""ceph-tpu: a TPU-native distributed object storage framework.

A from-scratch re-design of the capabilities of Ceph (reference: v11.0.2,
Kraken) built TPU-first: the math-heavy data-path kernels (GF(2^8)
Reed-Solomon erasure coding, CRC32C scrub checksumming) run as batched
JAX/XLA matmuls on TPU MXUs, the placement/consensus/storage tiers are
idiomatic Python + native C++ where performance demands it.

Layout (mirrors the reference layer map, SURVEY.md §1):
  ops/       device kernels: GF(2^8) math, bit-matrix matmuls, CRC32C
  erasure/   erasure-code plugin framework (tpu/jerasure/isa/shec/lrc)
  crush/     CRUSH placement (rjenkins, straw2, do_rule)
  kv/        key/value store abstraction (mem, sqlite)
  store/     ObjectStore: transactional local object storage
  msg/       typed, policy-driven async messenger
  mon/       paxos monitor cluster (maps, health, EC profiles)
  osd/       OSD data plane: PGs, replication, EC backend, scrub
  client/    objecter + librados-style client API
  utils/     config, logging, throttles, perf counters
  native/    C++ host kernels (AVX2 GF math, hw CRC32C) via ctypes
"""

__version__ = "0.1.0"
