"""The learner names its own work (doc/observability.md "Training and
compilation"): named scopes through both step programs, the compile
recorder that ``compile_cache.configure()`` installs, and the trainers'
two start-up spans. CPU, toy widths."""

import contextlib
import re
import time

import jax
import numpy as np
import pytest

from benchmark import scopes
from fishnet_tpu import telemetry
from fishnet_tpu.models.az import AzConfig
from fishnet_tpu.models.trunk import TrunkConfig
from fishnet_tpu.telemetry.registry import MetricsRegistry
from fishnet_tpu.telemetry.spans import EVENT_STAGES, RECORDER
from fishnet_tpu.train.az_trainer import AzTrainer
from fishnet_tpu.train.model import NetConfig
from fishnet_tpu.train.trainer import Trainer
from fishnet_tpu.utils import compile_cache

B = 4
TRACE, LOWER, BACKEND, LOAD = compile_cache.PHASE_OF_EVENT
HIT, MISS = compile_cache.CACHE_OF_EVENT

NNUE = NetConfig(num_features=64, max_active=4, l1=16, l2=4, l3=4, num_buckets=2, king_buckets=4)

MODEL_SCOPES = {
    "nnue": ("ft_gather", "ft_psqt", "pairwise", "stacks", "material"),
    "az": ("stem", "block00", "block01", "policy_head", "value_head"),
    # the sparse-expert trunk behind the same AzTrainer (models/trunk.py): one scope a part, the layer in its name
    "trunk": ("embed", "layer00.attention", "layer00.router", "layer00.dispatch", "layer00.experts", "layer00.combine",
              "layer01.attention", "layer01.experts", "final_norm", "policy_head", "value_head"),
}
TRUNK = TrunkConfig(hidden=32, heads=2, head_dim=16, layers=2, experts=4, experts_per_token=2, expert_width=16, value_hidden=8)


def make(kind):
    """A toy trainer of ``kind`` and one batch for it."""
    if kind == "nnue":
        trainer = Trainer(NNUE)
        rng = np.random.default_rng(0)
        rows = rng.integers(0, NNUE.block_rows + 4, (B, 2, 4))  # past the block: padding
        batch = {
            "indices": np.where(
                rows < NNUE.block_rows, rng.integers(0, 4, (B, 2, 1)) * NNUE.block_rows + rows, 64
            ).astype(np.int32),
            "buckets": rng.integers(0, 2, (B,)).astype(np.int32),
            "score_cp": np.zeros((B,), np.float32),
            "outcome": np.full((B,), 0.5, np.float32),
        }
    else:
        trainer = AzTrainer(TRUNK if kind == "trunk" else AzConfig(channels=8, blocks=2, value_hidden=8))
        batch = {
            "planes": np.zeros((B, 8, 8, 19), np.float32),
            "policy_target": np.full((B, 4672), 1 / 4672, np.float32),
            "value_target": np.zeros((B,), np.float32),
        }
    return trainer, batch


@contextlib.contextmanager
def persistent_cache(directory):
    """Compile against the persistent cache in ``directory``, or with none
    (None). The cache's key leaves scope names out, so a program it holds
    comes back with the names it was compiled with."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    before = jax.config.jax_compilation_cache_dir, jax.config.jax_enable_compilation_cache
    try:
        jax.config.update("jax_compilation_cache_dir", directory)
        jax.config.update("jax_enable_compilation_cache", directory is not None)
        cc.reset_cache()
        yield
    finally:
        jax.config.update("jax_compilation_cache_dir", before[0])
        jax.config.update("jax_enable_compilation_cache", before[1])
        cc.reset_cache()


def step_text(kind):
    trainer, batch = make(kind)
    state = jax.eval_shape(trainer._init, jax.random.PRNGKey(0))
    with persistent_cache(None):
        return trainer._step_jit.lower(state, batch).compile().as_text()


@pytest.fixture(scope="module", params=["nnue", "az", "trunk"])
def scoped(request):
    return request.param, step_text(request.param)


# -- A. names on the device ------------------------------------------------------


def test_step_text_holds_the_scope_contract(scoped):
    kind, text = scoped
    names = set(re.findall(r'op_name="([^"]*)"', text))
    held = {scope for name in names for part in scopes._parts(name) for scope in [scopes._unwrap(part)[1]]}
    assert {"forward", "loss", "optimizer"} <= held
    assert set(MODEL_SCOPES[kind]) <= held
    # the backward pass is JAX's own mark round the outermost scope
    assert any("transpose(jvp(forward))/" + MODEL_SCOPES[kind][0] in name for name in names)
    phases = {scopes.phase_of(name)[0] for name in names}
    assert {"forward", "backward", "optimizer"} <= phases


def test_no_heavy_instruction_is_unscoped(scoped):
    """Every convolution, dot, gather and scatter that carries a name
    carries one of the program's scopes. (The CPU compiler rewrites a few
    into instructions with no metadata at all, which no program can name:
    they are counted and have to stay few.)"""
    _kind, text = scoped
    heavy = re.compile(r"^\s+(?:ROOT )?%[\w.\-]+ = \S+ (convolution|dot|gather|scatter)\(")
    named = nameless = 0
    for line in text.splitlines():
        if heavy.match(line):
            op_name = scopes._OP_NAME.search(line)
            if op_name is None:
                nameless += 1
                continue
            named += 1
            assert scopes.phase_of(op_name.group(1))[0] != "unscoped", line[:300]
    assert named >= 3 and nameless <= named // 5


def test_table_gradient_is_named_and_holds_no_slot_temporary():
    """The NNUE table gradient (``model._table_grad``) runs under the
    scopes of the two tables it serves, in the backward pass, and only
    the forward pass still holds a [batch, 2, max_active, l1] array."""
    text = step_text("nnue")
    names = [m.group(1) for m in map(scopes._OP_NAME.search, text.splitlines()) if m]
    own = [n for n in names if re.search(r"tjr,tjn->trn|kt,trn->krn|argsort|searchsorted", n)]
    assert {tag for n in own for tag in re.findall(r"tjr,tjn->trn|kt,trn->krn|argsort", n)} == {
        "tjr,tjn->trn", "kt,trn->krn", "argsort"}
    for name in own:
        assert scopes.phase_of(name)[0] == "backward", name
        assert re.search(r"/transpose\(jvp\(forward\)\)/(ft_gather|ft_psqt)/", name), name
    slots = f"[{B},2,{NNUE.max_active},{NNUE.l1}]"
    holders = [line for line in text.splitlines() if slots in line]
    assert holders  # the forward's gather output
    for line in holders:
        name = scopes._OP_NAME.search(line)
        assert name and "jvp(forward)/ft_gather" in name.group(1) and "transpose(" not in name.group(1), line[:300]


def test_scopes_are_metadata_only(scoped, monkeypatch):
    kind, text = scoped
    monkeypatch.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
    bare = step_text(kind)
    assert "forward" not in set(re.findall(r'op_name="[^"]*?(forward|optimizer)', bare))
    assert bare != text
    assert scopes.without_metadata(bare) == scopes.without_metadata(text)
    assert "op_name" not in scopes.without_metadata(text)


# -- B. the compile recorder -----------------------------------------------------


def test_configure_twice_installs_one_listener():
    compile_cache.configure()
    recorder = compile_cache.RECORDER
    compile_cache.configure()
    assert compile_cache.configure_recorder() is recorder is compile_cache.RECORDER
    compiles = telemetry.REGISTRY.counter("fishnet_compiles_total", "", labelnames=("cache",))
    seconds = telemetry.REGISTRY.counter("fishnet_compile_seconds_total", "", labelnames=("phase",))
    before = compiles.value(cache="hit"), seconds.value(phase="lower"), len(recorder.events())
    jax.monitoring.record_event(HIT)  # one listener: counted once
    jax.monitoring.record_event_duration_secs(LOWER, 0.5, fun_name="jit(nothing)")
    assert compiles.value(cache="hit") == before[0] + 1
    assert seconds.value(phase="lower") == pytest.approx(before[1] + 0.5)
    assert len(recorder.events()) == min(before[2] + 3, compile_cache.EVENTS_KEPT)  # hit, trace (0 s), lower


def test_a_miss_then_a_hit_against_a_fresh_cache_directory(tmp_path):
    compile_cache.configure()
    hits = lambda: telemetry.REGISTRY.counter("fishnet_compiles_total", "", labelnames=("cache",))
    seconds = lambda: telemetry.REGISTRY.counter("fishnet_compile_seconds_total", "", labelnames=("phase",))
    with persistent_cache(str(tmp_path)):
        fn = lambda x: jax.numpy.tanh(x) * 3.25 + 0.125
        x = jax.numpy.ones((7,), jax.numpy.float32)
        x.block_until_ready()  # its own programs compile before the counts are read
        counts = {k: hits().value(cache=k) for k in ("hit", "miss")}
        started = time.monotonic()
        jax.jit(fn)(x).block_until_ready()
        assert hits().value(cache="miss") == counts["miss"] + 1
        assert hits().value(cache="hit") == counts["hit"]
        cold = compile_cache.RECORDER.totals_since(started)
        assert cold["cache_misses"] == 1 and cold["compile_s"] > 0 and cold["cache_load_s"] == 0
        assert cold["trace_lower_s"] > 0

        jax.clear_caches()
        loaded = seconds().value(phase="cache_load")
        started = time.monotonic()
        jax.jit(fn)(x).block_until_ready()
        assert hits().value(cache="hit") == counts["hit"] + 1
        assert hits().value(cache="miss") == counts["miss"] + 1
        assert seconds().value(phase="cache_load") > loaded
        warm = compile_cache.RECORDER.totals_since(started)
        assert warm["cache_misses"] == 0 and warm["cache_load_s"] > 0


def test_recorder_counts_disjoint_phases():
    """Fed the events of one cold and one warm program (names and order as
    JAX 0.9.0 sends them): nested traces count once, a load is not a compile."""
    registry = MetricsRegistry()
    recorder = compile_cache.CompileRecorder(registry)
    started = time.monotonic()
    for _ in range(600):  # more than the events kept: nested traces are not kept
        recorder.on_duration(TRACE, 0.001, fun_name="relu")
    recorder.on_duration(TRACE, 2.0, fun_name="_step")
    recorder.on_duration(LOWER, 0.5, fun_name="jit(_step)")
    recorder.on_event(MISS)
    recorder.on_duration(BACKEND, 30.0, fun_name="jit(_step)")
    recorder.on_duration("/jax/compilation_cache/compile_time_saved_sec", 9.0)  # not ours
    recorder.on_duration(TRACE, 1.0, fun_name="_step")
    recorder.on_duration(LOWER, 0.25, fun_name="jit(_step)")
    recorder.on_event(HIT)
    recorder.on_duration(LOAD, 1.5)
    recorder.on_duration(BACKEND, 1.75, fun_name="jit(_step)")
    seconds = registry.counter("fishnet_compile_seconds_total", "", labelnames=("phase",))
    assert seconds.value(phase="trace") == pytest.approx(3.0)
    assert seconds.value(phase="lower") == pytest.approx(0.75)
    assert seconds.value(phase="backend") == pytest.approx(30.25)
    assert seconds.value(phase="cache_load") == pytest.approx(1.5)
    compiles = registry.counter("fishnet_compiles_total", "", labelnames=("cache",))
    assert (compiles.value(cache="miss"), compiles.value(cache="hit")) == (1, 1)
    assert recorder.totals_since(started) == {
        "compile_s": pytest.approx(30.25), "cache_load_s": 1.5, "trace_lower_s": 3.75, "cache_misses": 1}
    assert len(recorder.events()) == 9 <= compile_cache.EVENTS_KEPT
    for _ in range(300):
        recorder.on_event(HIT)
    assert len(recorder.events()) == compile_cache.EVENTS_KEPT


# -- B. the start-up spans -------------------------------------------------------


@pytest.mark.parametrize("kind", ["nnue", "az"])
def test_startup_spans_once_a_trainer_with_telemetry_disabled(kind):
    assert not telemetry.enabled()
    assert {"train_init", "train_first_step"} <= set(EVENT_STAGES)
    started = time.monotonic()
    mine = lambda: [s for s in RECORDER.spans() if s["t"] >= started and s["stage"].startswith("train_")]
    trainer, batch = make(kind)
    state = trainer.init(1)
    state, _ = trainer.step(state, batch)
    spans = mine()
    assert [s["stage"] for s in spans] == ["train_init", "train_first_step"]
    for span in spans:
        assert span["trainer"] == kind and span["dur_ms"] > 0
        assert {"compile_s", "cache_load_s", "trace_lower_s", "cache_misses"} <= set(span)
        assert span["trace_lower_s"] > 0  # both programs were traced inside their spans
        assert span["compile_s"] + span["cache_load_s"] + span["trace_lower_s"] <= span["dur_ms"] / 1e3 + 1e-3
    state, metrics = trainer.step(state, batch)
    assert np.isfinite(float(metrics["loss"]))
    assert len(mine()) == 2  # a second step records nothing
    other, _ = make(kind)
    other.step(other.init(2), batch)
    assert [s["stage"] for s in mine()].count("train_first_step") == 2  # once a trainer INSTANCE
