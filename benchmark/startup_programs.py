"""The whole of start-up, read from the program's own spans.

Beside the two spans of ``benchmark/startup.py`` the program records
``process_boot`` (the process's start to the package's first import),
``program_import`` (from there to the first trainer's construction) and
one ``program_up`` for every program it brings up, with its name, its
four phases (``trace_s``, ``lower_s``, ``cache_load_s``, ``compile_s``),
``cache`` (``hit``, ``miss``, ``none``) and the functions traced inside it
that cost most (``fishnet_tpu/train/startup.py``,
``fishnet_tpu/utils/compile_cache.py``). Programs too small to be spans
are a running ``small`` pair (count, seconds) that the two trainer spans
carry as of their start and their end.

With them ``setup_s`` is six consecutive intervals: boot | import, up to
``train_init``'s start | ``train_init`` | between, up to
``train_first_step``'s start (the caller's: here the settle and the pool)
| ``train_first_step`` | warm-up, up to the window. The window starts at
``process_boot``'s start + ``setup_s`` (the runner's clock starts at the
interpreter's first line, ~0.03 s after the process). The cell's trainer
is the first the process makes, so the first span of each stage is read.
Once a run the reading prints the partition and the costliest programs.
A program that lacks a span (the parent of the PR that added them; a
platform without ``/proc``) gives None for the metrics that need it.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional

from benchmark import startup

PHASES = ("trace_s", "lower_s", "cache_load_s", "compile_s")
METRICS = ("setup_boot_s", "setup_import_s", "setup_between_s", "setup_between_programs_s", "setup_warmup_s", "setup_programs_missed")


def metric(ctx: Dict[str, Any], name: str) -> Optional[float]:
    """One of the six start-up metrics, from the run's one reading."""
    if "startup_programs" not in ctx:
        from fishnet_tpu.telemetry.spans import RECORDER

        ctx["startup_programs"] = read(RECORDER.spans(), ctx["setup_s"])
    return ctx["startup_programs"].get(name)


def _end(span: Dict[str, Any]) -> float:
    return span["t"] + span["dur_ms"] / 1e3


def _phases(span: Dict[str, Any]) -> float:
    return sum(span[phase] for phase in PHASES)


def read(spans: List[Dict[str, Any]], setup_s: float) -> Dict[str, float]:
    """The metrics that ``spans`` (oldest first) and ``setup_s`` give; where they give all six, two printed lines."""
    boot, imported, init, first_step = (
        next((span for span in spans if span["stage"] == stage), None) for stage in ("process_boot", "program_import", *startup.STAGES))
    programs = [span for span in spans if span["stage"] == "program_up"]
    out: Dict[str, float] = {}
    if boot:
        out["setup_boot_s"] = boot["dur_ms"] / 1e3
    if imported and init:
        out["setup_import_s"] = init["t"] - imported["t"]
    if init and first_step:
        out["setup_between_s"] = first_step["t"] - _end(init)
        if "small_at_end" in init and "small_at_start" in first_step:
            between = [span for span in programs if _end(init) <= _end(span) <= first_step["t"]]
            out["setup_between_programs_s"] = sum(map(_phases, between)) + first_step["small_at_start"][1] - init["small_at_end"][1]
    if boot and first_step:
        window = boot["t"] + setup_s
        out["setup_warmup_s"] = window - _end(first_step)
        programs = [span for span in programs if _end(span) <= window]
        out["setup_programs_missed"] = sum(span["cache"] == "miss" for span in programs)
    if len(out) == len(METRICS):
        _print(out, init["dur_ms"] / 1e3, first_step["dur_ms"] / 1e3, setup_s, sorted(programs, key=_phases, reverse=True)[:5])
    return out


def _print(out: Dict[str, float], init_s: float, first_step_s: float, setup_s: float, costliest: List[Dict[str, Any]]) -> None:
    whole = out["setup_boot_s"] + out["setup_import_s"] + init_s + out["setup_between_s"] + first_step_s + out["setup_warmup_s"]
    print(
        f"start-up: boot {out['setup_boot_s']:.1f} | import {out['setup_import_s']:.1f} | init {init_s:.1f} | "
        f"between {out['setup_between_s']:.1f} (programs {out['setup_between_programs_s']:.1f}) | first step {first_step_s:.1f} | "
        f"warm-up {out['setup_warmup_s']:.1f} = {whole:.1f} of setup_s {setup_s:.1f}"
    )
    lines = [f"{span['name']} {_phases(span):.2f} s (" + " ".join(f"{phase[:-2]} {span[phase]:.2f}" for phase in PHASES) + f", {span['cache']})"
             for span in costliest]
    if costliest:
        lines.append(f"traced in {costliest[0]['name']}: " + ", ".join(f"{name} x{calls} {self_s:.2f}" for name, calls, self_s in costliest[0]["traced"]))
    print("start-up programs: " + "; ".join(lines))
