"""The position pool every training cell draws from.

A copy of ``fishnet_tpu.train.data.playout_positions`` (random legal
playouts from the start position), kept here so that no later PR can
change the traffic: it walks games with the program's rules library and
hands each kept position to the family's encoder, which calls the
program's own encoders. Everything comes from the seed.
"""

from __future__ import annotations

from typing import Any, Dict

import numpy as np

from fishnet_tpu.chess.board import Board
from fishnet_tpu.protocol.types import STARTPOS


def playout_pool(traffic: Dict[str, Any], seed: int, family: Any, n_positions: int = 0) -> Dict[str, np.ndarray]:
    """``n_positions`` distinct legal positions (the traffic file's
    ``pool_positions`` unless the caller needs fewer), encoded by the
    family's ``PoolEncoder``: ``add(board, fen, moves)`` for each position
    kept, then ``finish(white_scores, rng, traffic)`` for the labels."""
    n_positions = n_positions or int(traffic["pool_positions"])
    encoder = family.PoolEncoder(n_positions)
    max_plies, skip_first = int(traffic["max_plies"]), int(traffic["skip_first"])
    rng = np.random.default_rng([int(seed), 0x706F6F6C])
    seen = set()
    white_scores = []
    while len(white_scores) < n_positions:
        board = Board(STARTPOS)
        kept = 0
        result = 0.5
        for ply in range(max_plies):
            moves = board.legal_moves()
            outcome = board.outcome()
            if outcome != Board.ONGOING or not moves:
                if outcome == Board.CHECKMATE:
                    result = 0.0 if board.turn() == "w" else 1.0
                break
            if ply >= skip_first and len(white_scores) + kept < n_positions:
                fen = board.fen()
                key = fen.rsplit(" ", 2)[0]  # placement, turn, castling, en passant
                if key not in seen:
                    seen.add(key)
                    encoder.add(board, fen, moves)
                    kept += 1
            board.push_uci(moves[int(rng.integers(len(moves)))])
        white_scores.extend([result] * kept)
    return encoder.finish(np.asarray(white_scores, np.float32), rng, traffic)
