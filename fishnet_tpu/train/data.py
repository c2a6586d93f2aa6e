"""NNUE training-data generation: positions + teacher labels.

The standard NNUE recipe trains on (position, teacher score, game
outcome) triples. The reference consumes nets trained elsewhere; here
the framework generates its own data: positions come from playouts (or
any FEN source, e.g. acquired games), teacher scores come from the
framework's own batched search service — every labeling search shares
the same TPU microbatches as serving, so labeling throughput scales
with batch width — and outcomes come from the game results.

Output batches feed fishnet_tpu.train.Trainer directly.

``block_noise`` is the other trainer's: the noise a block-diffusion trunk
is trained under (``models/trunk.py``, the ninth block), which every
maker of its batches draws here and nowhere else.
"""

from __future__ import annotations

import asyncio
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

from fishnet_tpu.chess.board import Board
from fishnet_tpu.protocol.types import STARTPOS
from fishnet_tpu.search.service import SearchService


def block_noise(rng: np.random.Generator, boards: int, block_length: int, t_min: float = 1e-3) -> Tuple[np.ndarray, np.ndarray]:
    """The noise of ``boards`` boards under block diffusion with blocks
    of ``block_length`` squares (the linear schedule of MDLM / BD3-LMs):
    ``block_level`` float32 ``[boards, 64 / block_length]``, a level ``t``
    a board and block, uniform on ``[t_min, 1]``, and ``square_masked``
    bool ``[boards, 64]``, each square masked independently with its
    block's level as the probability. THE maker of this noise: the
    learner's batches (``train/selfplay.py selfplay_batch``) and the
    benchmark's (``benchmark/families/sdar_trunk.py``) call it, and
    ``AzTrainer``'s step reads the two arrays from its batch and draws
    nothing. ``t_min`` over 0 keeps the loss's ``1 / t`` finite."""
    if not 0 < block_length <= 64 or 64 % block_length or not 0.0 < t_min <= 1.0:
        raise ValueError(f"blocks of {block_length} squares do not divide a board's 64, or t_min {t_min} is not in (0, 1]")
    block_level = rng.uniform(t_min, 1.0, (boards, 64 // block_length)).astype(np.float32)
    return block_level, rng.random((boards, 64)) < np.repeat(block_level, block_length, axis=1)


def playout_positions(
    n_games: int = 8,
    max_plies: int = 60,
    seed: int = 0,
    skip_first: int = 6,
) -> List[Tuple[str, float]]:
    """Random playouts from the start position. Returns (fen,
    white_score) pairs where white_score is the game result for white in
    {0, 0.5, 1}; positions from the opening book-ish first plies are
    skipped (they are all near-equal and teach nothing)."""
    rng = np.random.default_rng(seed)
    out: List[Tuple[str, float]] = []
    for _ in range(n_games):
        board = Board(STARTPOS)
        fens: List[str] = []
        result = 0.5
        for ply in range(max_plies):
            moves = board.legal_moves()
            outcome = board.outcome()
            if outcome != Board.ONGOING or not moves:
                if outcome == Board.CHECKMATE:
                    result = 0.0 if board.turn() == "w" else 1.0
                else:
                    result = 0.5
                break
            if ply >= skip_first:
                fens.append(board.fen())
            board.push_uci(moves[int(rng.integers(len(moves)))])
        out.extend((fen, result) for fen in fens)
    return out


async def label_positions(
    service: SearchService,
    positions: Sequence[Tuple[str, float]],
    nodes: int = 2000,
) -> Dict[str, np.ndarray]:
    """Teacher-label positions with fixed-node searches (all batched
    through the shared service) and pack an NNUE training batch.

    Returns the Trainer's batch dict: indices int32 [B,2,32] (stm
    perspective, sentinel-padded), buckets int32 [B], score_cp float32
    [B] (from the side to move), outcome float32 [B] in {0,.5,1} from
    the side to move's perspective."""
    boards = [Board(fen) for fen, _ in positions]
    results = await asyncio.gather(
        *(service.search(fen, [], nodes=nodes) for fen, _ in positions)
    )

    indices = []
    buckets = []
    scores = []
    outcomes = []
    for (fen, white_score), board, result in zip(positions, boards, results):
        # One line per (iteration depth, rank): the LAST multipv-1 entry
        # is the deepest completed iteration — that's the teacher score.
        line = None
        for l in result.lines:
            if l.multipv == 1:
                line = l
        if line is None:
            continue
        cp = float(np.clip(line.value if not line.is_mate
                           else (30000 if line.value > 0 else -30000),
                           -30000, 30000))
        idx, bucket = board.nnue_features()
        indices.append(idx)
        buckets.append(bucket)
        scores.append(cp)
        stm_white = board.turn() == "w"
        outcomes.append(white_score if stm_white else 1.0 - white_score)
    if not indices:
        # Nothing survived (no positions, or every search failed): an
        # empty batch is a valid answer the trainer loop can skip.
        return {
            "indices": np.zeros((0, 2, 32), np.int32),
            "buckets": np.zeros((0,), np.int32),
            "score_cp": np.zeros((0,), np.float32),
            "outcome": np.zeros((0,), np.float32),
        }
    return {
        "indices": np.stack(indices).astype(np.int32),
        "buckets": np.asarray(buckets, np.int32),
        "score_cp": np.asarray(scores, np.float32),
        "outcome": np.asarray(outcomes, np.float32),
    }
