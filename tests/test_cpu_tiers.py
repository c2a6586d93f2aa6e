"""CPU feature detection and native-library tier selection (the
reference's assets.rs tier cascade + AMD slow-PEXT heuristic)."""

import ctypes
import json
import os
import signal
import subprocess
import sys
import time
from pathlib import Path

import pytest

from fishnet_tpu.chess.cpu import CpuInfo, parse_cpuinfo

CPP_DIR = Path(__file__).resolve().parent.parent / "cpp"

INTEL_V3 = """\
vendor_id\t: GenuineIntel
cpu family\t: 6
flags\t\t: fpu sse4_1 sse4_2 popcnt avx avx2 bmi1 bmi2
"""

AMD_ZEN2 = """\
vendor_id\t: AuthenticAMD
cpu family\t: 23
flags\t\t: fpu sse4_1 sse4_2 popcnt avx avx2 bmi1 bmi2
"""

AMD_ZEN3 = """\
vendor_id\t: AuthenticAMD
cpu family\t: 25
flags\t\t: fpu sse4_1 sse4_2 popcnt avx avx2 bmi1 bmi2
"""

OLD_BOX = """\
vendor_id\t: GenuineIntel
cpu family\t: 6
flags\t\t: fpu sse2 sse4_1 sse4_2 popcnt
"""


def test_intel_gets_v3():
    info = parse_cpuinfo(INTEL_V3)
    assert info.fast_pext
    assert info.best_tier() == "v3"


def test_amd_zen2_pext_demoted_to_v2():
    # BMI2 present but microcoded: the reference demotes exactly this
    # case (assets.rs:94-108).
    info = parse_cpuinfo(AMD_ZEN2)
    assert not info.fast_pext
    assert info.best_tier() == "v2"


def test_amd_zen3_gets_v3():
    info = parse_cpuinfo(AMD_ZEN3)
    assert info.fast_pext
    assert info.best_tier() == "v3"


def test_old_cpu_gets_v2():
    assert parse_cpuinfo(OLD_BOX).best_tier() == "v2"


def test_unknown_cpu_gets_none():
    assert CpuInfo().best_tier() is None


def test_aarch64_gets_arm64_tier():
    # aarch64 /proc/cpuinfo has no x86 flags line; the arch field alone
    # selects the single armv8 tier (reference build.rs:187-276 ships an
    # armv8 engine build the same way).
    info = CpuInfo(arch="aarch64")
    assert info.best_tier() == "arm64"
    assert CpuInfo(arch="x86_64").best_tier() is None


@pytest.mark.slow
def test_tier_builds_load_and_pass_perft():
    import platform

    if platform.machine() not in ("x86_64", "AMD64"):
        pytest.skip("x86-64 tier builds")
    subprocess.run(["make", "-C", str(CPP_DIR), "tiers", "-j2"], check=True,
                   capture_output=True)
    # Only EXECUTE tiers the host can run: dlopen of a higher tier
    # succeeds, but its instructions SIGILL the whole process (e.g. v4
    # on a non-AVX-512 CI runner). best_tier() ranks host capability.
    from fishnet_tpu.chess.cpu import detect

    rank = {"v2": 2, "v3": 3, "v4": 4}
    host = rank.get(detect().best_tier() or "", 0)
    runnable = [t for t in ("v2", "v3", "v4") if rank[t] <= host]
    assert runnable, "host below x86-64-v2; tier artifacts unusable here"
    for tier in runnable:
        lib = ctypes.CDLL(str(CPP_DIR / f"libfishnetcore-{tier}.so"))
        lib.fc_init()
        err = ctypes.create_string_buffer(256)
        lib.fc_pos_new.restype = ctypes.c_void_p
        pos = lib.fc_pos_new(
            b"rnbqkbnr/pppppppp/8/8/8/8/PPPPPPPP/RNBQKBNR w KQkq - 0 1",
            0, err, 256,
        )
        assert pos
        lib.fc_perft.restype = ctypes.c_uint64
        assert lib.fc_perft(ctypes.c_void_p(pos), 4) == 197281


def test_avx512_gets_v4():
    info = CpuInfo(
        vendor="GenuineIntel", family=6,
        flags=frozenset({
            "sse4_2", "popcnt", "avx2", "bmi2", "avx512f", "avx512bw",
            "avx512cd", "avx512dq", "avx512vl",
        }),
    )
    assert info.best_tier() == "v4"
    # Pre-Zen4-style AMD with microcoded PEXT: demoted past v4 AND v3.
    amd = CpuInfo(vendor="AuthenticAMD", family=0x17, flags=info.flags)
    assert amd.best_tier() == "v2"


# ---------------------------------------------------------------------
# cpp/Makefile's link rule under parallel first builds: tier-1 runs six
# workers, and on a tree with no built library each one's first
# core.load() runs `make` while the others build or load. The REAL
# Makefile's libfishnetcore.so rule runs here in a scratch directory
# with a slow stand-in for the compiler (CXX, SRC and HDR overridden on
# the command line): the subject is the rule, not g++.

LIB = "libfishnetcore.so"
LINES = 20

#: Stands in for g++: writes its `-o` file a line every 10 ms, so that a
#: reader has time to catch it half-written. FAKE_CC=stall writes half,
#: touches `stalled` and waits to be killed; FAKE_CC=fail writes half
#: and exits 1.
FAKE_CC = f"""\
import os, sys, time
out = sys.argv[sys.argv.index("-o") + 1]
mode = os.environ.get("FAKE_CC", "")
with open(out, "w") as f:
    for i in range({LINES}):
        if mode and i == {LINES} // 2:
            f.flush()
            if mode == "fail":
                sys.exit(1)
            open("stalled", "w").close()
            time.sleep(120)
        f.write(f"{{os.getpid()}} {{i}}\\n")
        f.flush()
        time.sleep(0.01)
    f.write(f"END {{os.getpid()}}\\n")
"""

#: One test worker on a fresh tree: build, then load while others build.
BUILD_THEN_READ = """\
import json, subprocess, sys, time
make, lib, stagger = json.loads(sys.argv[1]), sys.argv[2], float(sys.argv[3])
time.sleep(stagger)
rc = subprocess.run(make, capture_output=True).returncode
reads = []
until = time.monotonic() + 0.8
while time.monotonic() < until:
    try:
        reads.append(open(lib).read())
    except OSError as e:
        reads.append(repr(e))
    time.sleep(0.02)
print(json.dumps({"rc": rc, "reads": reads}))
"""


def _whole(text):
    """True for a file one run of FAKE_CC wrote from its first line to
    its last: what a loadable library is to the real rule."""
    lines = text.splitlines()
    if len(lines) != LINES + 1 or not lines[-1].startswith("END "):
        return False
    pid = lines[-1].split()[1]
    return lines[:-1] == [f"{pid} {i}" for i in range(LINES)]


def _scratch_build(tmp_path):
    """The make command that runs the real rule in ``tmp_path``."""
    (tmp_path / "cc.py").write_text(FAKE_CC)
    (tmp_path / "src.cpp").write_text("")
    return [
        "make", "-B", "-C", str(tmp_path), "-f", str(CPP_DIR / "Makefile"),
        LIB, f"CXX={sys.executable} {tmp_path / 'cc.py'}", "SRC=src.cpp",
        "HDR=",
    ]


def test_parallel_builds_and_loads_see_whole_libraries(tmp_path):
    make = _scratch_build(tmp_path)
    workers = [
        subprocess.Popen(
            [sys.executable, "-c", BUILD_THEN_READ, json.dumps(make),
             str(tmp_path / LIB), str(0.15 * i)],
            stdout=subprocess.PIPE, text=True,
        )
        for i in range(4)
    ]
    results = [json.loads(w.communicate(timeout=120)[0]) for w in workers]
    assert [r["rc"] for r in results] == [0] * 4
    reads = [text for r in results for text in r["reads"]]
    assert len(reads) >= 4 * 5
    # `file too short` at dlopen is a reader catching a link half-way.
    assert [text for text in reads if not _whole(text)] == []
    assert list(tmp_path.glob("*.tmp")) == []


@pytest.mark.parametrize("how", ["killed", "failed"])
def test_unfinished_link_leaves_the_previous_whole_library(tmp_path, how):
    make = _scratch_build(tmp_path)
    subprocess.run(make, check=True, capture_output=True)
    previous = (tmp_path / LIB).read_text()
    assert _whole(previous)
    env = {**os.environ, "FAKE_CC": "stall" if how == "killed" else "fail"}
    second = subprocess.Popen(
        make, env=env, stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
        start_new_session=True,
    )
    if how == "killed":
        deadline = time.monotonic() + 60
        while not (tmp_path / "stalled").exists():
            assert time.monotonic() < deadline, "the link never started"
            assert second.poll() is None
            time.sleep(0.02)
        os.killpg(second.pid, signal.SIGTERM)  # half-way through the write
    assert second.wait(timeout=60) != 0
    assert (tmp_path / LIB).read_text() == previous
    # The recipe's shell removes its temporary as it exits, which may be
    # a moment after `make` itself has gone.
    deadline = time.monotonic() + 10
    while list(tmp_path.glob("*.tmp")) and time.monotonic() < deadline:
        time.sleep(0.02)
    assert list(tmp_path.glob("*.tmp")) == []


@pytest.mark.parametrize(
    "target",
    [LIB, "libfishnetcore-v2.so", "libfishnetcore-v3.so",
     "libfishnetcore-v4.so", "libfishnetcore-arm64.so"],
)
def test_every_library_rule_links_under_another_name(target):
    """The real cpp/Makefile, dry run: the one linking line of a library
    rule writes `<library>.<pid>.tmp` and ends by renaming it onto the
    library."""
    recipe = subprocess.run(
        ["make", "-C", str(CPP_DIR), "-n", "-B", target],
        check=True, capture_output=True, text=True,
    ).stdout
    links = [line for line in recipe.splitlines() if " -shared " in line]
    assert len(links) == 1, recipe
    link = links[0].rstrip()
    assert link.startswith(f't="{target}.$$.tmp"; '), link
    assert ' -o "$t" ' in link and f"-o {target}" not in link
    assert link.endswith(f'&& mv -f "$t" "{target}"'), link
