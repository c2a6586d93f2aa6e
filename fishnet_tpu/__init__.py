"""fishnet-tpu: a TPU-native distributed chess-analysis framework.

A brand-new implementation with the capabilities of lichess.org's fishnet
client (reference surveyed in SURVEY.md): it speaks the fishnet HTTP/JSON
work protocol (acquire / analysis / move / abort / status), validates and
expands acquired games into per-ply positions, schedules them across search
workers, and reports PVs and centipawn/mate scores.

Unlike the reference (one single-threaded Stockfish subprocess per CPU core,
cf. /root/reference/src/main.rs:158-170), the engine tier here is a C++
search core whose leaf evaluations are *batched* onto TPU: all concurrent
searches yield positions into a microbatcher that executes one large
JAX/Pallas NNUE forward per step, sharded across a `jax.sharding.Mesh`.

Package layout:
    protocol/   wire model (JSON types of doc/protocol.md)
    net/        HTTP communication backend (the only server-facing I/O)
    sched/      queue scheduler: batch expansion, reassembly, pacing
    chess/      chess rules (ctypes bindings over the C++ core)
    engine/     engine drivers behind the reference's stockfish.rs seam
    nnue/       HalfKAv2_hm feature extraction, .nnue weights, JAX eval
    ops/        Pallas TPU kernels
    models/     model families (NNUE, AlphaZero-style policy+value)
    search/     batched search orchestration, MCTS
    parallel/   device mesh / sharding utilities
    train/      distributed training steps (NNUE, AZ)
    utils/      logger, stats, backoff, config, assets
"""

import time

#: ``time.monotonic()`` of the package's first import: where the ``process_boot`` span ends and
#: ``program_import`` starts (train/startup.py).
FIRST_IMPORT = time.monotonic()

from fishnet_tpu.version import __version__  # noqa: E402

__all__ = ["__version__"]
