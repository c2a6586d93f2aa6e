"""The UCI server front-end, driven as a real subprocess over pipes."""

import asyncio
import os
import sys

import pytest

pytestmark = pytest.mark.anyio

ENV = {
    **os.environ,
    "JAX_PLATFORMS": "cpu",
}


async def drive(commands, patterns, timeout=120):
    """Send commands; collect output until all patterns appear (in order)."""
    proc = await asyncio.create_subprocess_exec(
        sys.executable, "-m", "fishnet_tpu", "uci",
        "--no-conf", "--no-stats-file", "--microbatch", "64",
        env=ENV,
        stdin=asyncio.subprocess.PIPE,
        stdout=asyncio.subprocess.PIPE,
        stderr=asyncio.subprocess.DEVNULL,
    )
    try:
        proc.stdin.write(("\n".join(commands) + "\n").encode())
        await proc.stdin.drain()
        lines = []
        remaining = list(patterns)

        async def read():
            while remaining:
                raw = await proc.stdout.readline()
                if not raw:
                    break
                line = raw.decode().strip()
                lines.append(line)
                if remaining and remaining[0] in line:
                    remaining.pop(0)

        await asyncio.wait_for(read(), timeout)
        assert not remaining, f"missing {remaining!r} in output:\n" + "\n".join(lines)
        return lines
    finally:
        try:
            proc.stdin.write(b"quit\n")
            await proc.stdin.drain()
            await asyncio.wait_for(proc.wait(), 15)
        except Exception:  # noqa: BLE001
            try:
                proc.kill()
            except ProcessLookupError:
                pass


async def test_uci_handshake_and_mate():
    lines = await drive(
        [
            "uci",
            "isready",
            "position fen 6k1/5ppp/8/8/8/8/5PPP/3R2K1 w - - 0 1",
            "go depth 4",
        ],
        ["uciok", "readyok", "bestmove d1d8"],
    )
    assert any("id name fishnet-tpu" in l for l in lines)
    assert any("score mate 1" in l for l in lines)


async def test_uci_position_moves_and_nodes():
    lines = await drive(
        [
            "uci",
            "position startpos moves e2e4 e7e5",
            "go nodes 3000",
        ],
        ["uciok", "bestmove"],
    )
    infos = [l for l in lines if l.startswith("info depth")]
    assert infos and all("pv" in l for l in infos)


async def test_uci_variant_option():
    await drive(
        [
            "uci",
            "setoption name UCI_Variant value kingofthehill",
            "position fen 4k3/8/8/8/8/4K3/8/8 w - - 0 1",
            "go depth 4",
        ],
        ["uciok", "bestmove e3"],
    )


async def test_uci_clock_maps_to_movetime():
    # wtime/btime must bound the search (no depth-12 default ignoring the
    # clock): 2 s clocks -> ~50ms+ movetime, finishes well within timeout.
    await drive(
        [
            "uci",
            "position startpos moves e2e4",
            "go wtime 2000 btime 2000 winc 0 binc 0",
        ],
        ["uciok", "bestmove"],
        timeout=60,
    )


async def test_uci_malformed_go_is_ignored_not_fatal():
    await drive(
        [
            "uci",
            "position startpos",
            "go depth x movetime abc nodes 800",  # malformed tokens ignored
        ],
        ["uciok", "bestmove"],
    )


async def test_uci_second_go_supersedes_infinite():
    await drive(
        [
            "uci",
            "position startpos",
            "go infinite",
            "go depth 3",  # must cancel the infinite search, not hang
        ],
        ["uciok", "bestmove"],
        timeout=120,
    )


async def test_uci_stop_infinite():
    await drive(
        [
            "uci",
            "position startpos",
            "go infinite",
            "stop",
        ],
        ["uciok", "bestmove"],
        timeout=150,
    )
