"""The learner names its own work (doc/observability.md "Training and
compilation"): named scopes through both step programs, the compile
recorder that ``compile_cache.configure()`` installs, and the trainers'
two start-up spans. CPU, toy widths."""

import contextlib
import os
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
from fishnet_tpu.train import step_metrics
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
    # its fourth block: one sublayer a layer, the scan's core beside the mixer's scope and never inside it
    "pattern": ("embed", "layer00.mamba", "layer00.scan", "layer01.router", "layer01.dispatch", "layer01.experts", "layer01.combine",
                "layer01.shared", "layer02.attention", "final_norm", "policy_head", "value_head"),
    # the second block's feed-forwards: a leading dense layer, a shared expert beside the held share
    "share": ("embed", "layer00.attention", "layer00.dense", "layer01.attention", "layer01.router", "layer01.dispatch", "layer01.experts",
              "layer01.combine", "layer01.shared", "final_norm", "policy_head", "value_head"),
    # the third's: the way through the latent beside the attention's scope, in every layer
    "latent": ("embed", "layer00.attention", "layer00.latent", "layer00.dense", "layer01.attention", "layer01.latent", "layer01.router",
               "layer01.dispatch", "layer01.experts", "layer01.combine", "layer01.shared", "final_norm", "policy_head", "value_head"),
    # the fifth's: the mix of queries and keys beside the attention's scope
    "cca": ("embed", "layer00.attention", "layer00.cca", "layer00.router", "layer00.dispatch", "layer00.experts", "layer00.combine",
            "layer01.attention", "layer01.cca", "layer01.router", "layer01.dispatch", "layer01.experts", "layer01.combine", "final_norm",
            "policy_head", "value_head"),
    # the sixth's: the mixer told by layer, the delta rule's core beside a KDA layer's scope and never inside it, the latent layer as the third's
    "kda": ("embed", "layer00.kda", "layer00.delta", "layer00.dense", "layer01.attention", "layer01.latent", "layer01.router", "layer01.dispatch",
            "layer01.experts", "layer01.combine", "layer01.shared", "final_norm", "policy_head", "value_head"),
    # the seventh's: the delta rule's core beside a GDN layer's scope and never inside it, the gated attention layer under the first kind's
    # scope, every layer routed, the shared expert with its token gate under its own
    "gdn": ("embed", "layer00.gdn", "layer00.delta", "layer00.router", "layer00.dispatch", "layer00.experts", "layer00.combine", "layer00.shared",
            "layer01.attention", "layer01.router", "layer01.dispatch", "layer01.experts", "layer01.combine", "layer01.shared", "final_norm",
            "policy_head", "value_head"),
}
TRUNK = TrunkConfig(hidden=32, heads=2, head_dim=16, layers=2, experts=4, experts_per_token=2, expert_width=16, value_hidden=8)
# a share of the experts held and balanced (the second block's routing), and the same with latent attention (the third's)
SHARE = TrunkConfig(hidden=32, heads=2, head_dim=16, layers=2, experts=8, experts_per_token=2, expert_width=16, value_hidden=8,
                    dense_layers=1, dense_width=32, shared_width=16, router_score="sigmoid", route_norm=True, held_experts=(2, 4), balance_rate=0.001)
LATENT = TrunkConfig(**{**SHARE.__dict__, "kv_lora_rank": 16, "qk_nope_head_dim": 16, "qk_rope_head_dim": 64, "v_head_dim": 16})
# the fourth block: a layer pattern, Mamba-2 mixers, ungated experts, a share held and balanced
PATTERN = TrunkConfig(hidden=32, heads=2, kv_heads=1, head_dim=16, qk_norm=False, pattern="ME*", experts=8, experts_per_token=2, expert_width=16,
                      gated_ffn=False, shared_width=8, value_hidden=8, mamba_heads=2, mamba_head_dim=8, mamba_groups=1, state_size=8,
                      router_score="sigmoid", route_norm=True, held_experts=(2, 4), balance_rate=0.001)
# the fifth block: compressed convolutional attention, an MLP router, one expert a token
CCA = TrunkConfig(hidden=32, heads=4, kv_heads=2, head_dim=8, layers=2, cca=(2, 2), rotary_dim=4, router_hidden=8, experts=8, experts_per_token=1,
                  expert_width=16, value_hidden=8, held_experts=(2, 4), balance_rate=0.001)
# the sixth block: a Kimi Delta Attention layer, then a latent layer without RoPE
KDA = TrunkConfig(**{**LATENT.__dict__, "mixers": ("kda", "latent"), "nope_layers": (1,), "kda_heads": 2, "kda_head_dim": 16})
# the seventh block: a Gated DeltaNet layer (two value heads a key head), then a gated attention layer with part of a head rotated
GDN = TrunkConfig(hidden=32, heads=2, kv_heads=1, head_dim=16, experts=8, experts_per_token=2, expert_width=16, value_hidden=8, gated_attention=True,
                  rotary_dim=4, shared_width=16, route_norm=True, held_experts=(2, 4), balance_rate=0.001, mixers=("gdn", "attention"),
                  linear_num_key_heads=1, linear_num_value_heads=2, linear_key_head_dim=16, linear_value_head_dim=16, shared_token_gate=True,
                  zero_centered_norms=True)
TRUNKS = {"trunk": TRUNK, "share": SHARE, "latent": LATENT, "pattern": PATTERN, "cca": CCA, "kda": KDA, "gdn": GDN}


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
        trainer = AzTrainer(TRUNKS.get(kind) or AzConfig(channels=8, blocks=2, value_hidden=8))
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


@pytest.fixture(scope="module", params=["nnue", "az", "trunk", "pattern"])
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


@pytest.mark.parametrize("kind", ["share", "latent", "cca", "kda", "gdn"])
def test_a_blocks_own_scopes_are_exactly_the_parents(kind):
    """The scopes of the trunk's own parts in the configurations the
    fixture above does not compile, as PR 46's PARENT (3036d35) named them
    (the sixth block's as PR 47 brought them, the seventh's as PR 51), no more and no fewer: the
    benchmark's reducers read these names, and a lowered step's text (the
    step pins) carries none of them."""
    names = set(re.findall(r'op_name="([^"]*)"', step_text(kind)))
    held = {scope for name in names for part in scopes._parts(name) for scope in [scopes._unwrap(part)[1]]}
    own = {scope for scope in held if re.match(r"layer\d\d\.|embed$|final_norm$", scope)}
    assert own == set(MODEL_SCOPES[kind]) - {"policy_head", "value_head"} and {"policy_head", "value_head"} <= held


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


# -- C. the step recorder ----------------------------------------------------------

LOSSES = {"nnue": {"loss", "pred_cp_mean", "pred_cp_abs"}, "az": {"loss", "policy_loss", "value_loss"}}
ROUTING = {"expert_load_max", "expert_load_min", "router_entropy", "moved_rows"}
STEP_KEYS = {
    "nnue": LOSSES["nnue"] | {"ft_block_misses"},
    "az": LOSSES["az"],
    "trunk": LOSSES["az"] | ROUTING,
    "share": LOSSES["az"] | ROUTING | {"held_slots", "expert_bias_abs_max"},
    "latent": LOSSES["az"] | ROUTING | {"held_slots", "expert_bias_abs_max", "latent_rms"},
    "pattern": LOSSES["az"] | ROUTING | {"held_slots", "expert_bias_abs_max", "ssm_dt_mean", "ssm_decay_min"},
    "kda": LOSSES["az"] | ROUTING | {"held_slots", "expert_bias_abs_max", "latent_rms", "kda_state_kept", "kda_beta"},
}


@pytest.fixture()
def steps(monkeypatch):
    """The process's step recorder, empty, on a registry of its own."""
    registry = MetricsRegistry()
    recorder = step_metrics.StepRecorder(registry)
    monkeypatch.setattr(step_metrics, "STEPS", recorder)
    return recorder, registry


def series(registry, name):
    return {tuple(sorted(s.labels.items())): s.value for fam in registry.collect() if fam.name == name for s in fam.samples}


@pytest.mark.parametrize("kind", ["nnue", "az"])
def test_ring_holds_the_last_steps_and_no_more(kind, steps):
    trainer, batch = make(kind)
    state = trainer.init(0)
    record = trainer._record
    assert record.read() == step_metrics.Reading(f"{kind}-0", 0, []) and record.read().steps == 0
    losses = []
    for _ in range(step_metrics.RING_STEPS + 3):
        state, metrics = trainer.step(state, batch)
        losses.append(metrics["loss"])
    reading = record.read()
    assert (reading.trainer, reading.steps, reading.first) == (f"{kind}-0", step_metrics.RING_STEPS + 3, 3)
    assert len(reading.metrics) == step_metrics.RING_STEPS == len(record._ring)
    assert [step["loss"] for step in reading.metrics] == [float(x) for x in losses[3:]]  # oldest first, the first three gone
    assert all(set(step) == STEP_KEYS[kind] and all(type(v) is float for v in step.values()) for step in reading.metrics)
    newest = record.read(last=2)
    assert (newest.first, newest.steps, newest.metrics) == (reading.steps - 2, reading.steps, reading.metrics[-2:])


@pytest.mark.parametrize("kind", ["nnue", "trunk"])
def test_step_transfers_nothing_and_takes_no_shared_lock(kind, steps, monkeypatch):
    """After its first call ``.step`` stores what the program returned and
    fetches nothing, and it goes on while another thread holds every lock
    a reader or a new trainer takes."""
    import threading

    recorder, registry = steps
    trainer, batch = make(kind)
    batch = jax.device_put(batch)
    state, _ = trainer.step(trainer.init(0), batch)

    def refuse(*_a, **_kw):
        raise AssertionError("a host transfer on the step's path")

    returned, failures = [], []

    def three_steps(state):
        try:
            with jax.transfer_guard_device_to_host("disallow_explicit"):
                for _ in range(3):
                    state, metrics = trainer.step(state, batch)
                    returned.append(metrics)
        except BaseException as err:  # handed to the test's thread, which raises it
            failures.append(err)

    with monkeypatch.context() as patched:
        patched.setattr(jax, "device_get", refuse)
        patched.setattr(jax, "block_until_ready", refuse)
        worker = threading.Thread(target=three_steps, args=(state,))
        with recorder._lock, registry._scrape_lock, registry._lock:
            worker.start()
            worker.join(timeout=120)
            assert not worker.is_alive(), "a step waited for a lock a reader holds"
    assert not failures, failures
    held = list(trainer._record._ring)
    assert [step for step, _metrics in held] == [0, 1, 2, 3]
    for (_step, kept), metrics in zip(held[1:], returned):
        assert kept is metrics  # the dict the step program returned, as it is
        assert all(isinstance(value, jax.Array) for value in kept.values())
    assert trainer._record.read().metrics[-1].keys() == STEP_KEYS[kind]


def test_first_stepped_trainer_with_others_made_round_it(steps):
    recorder, _registry = steps
    idle_before, _ = make("az")
    cell, batch = make("nnue")
    assert recorder.first_stepped() is None
    state, _ = cell.step(cell.init(0), batch)
    later, later_batch = make("az")
    later.step(later.init(0), later_batch)
    later.step(later.init(1), later_batch)
    idle_after, _ = make("nnue")
    assert [(r.trainer, r.steps) for r in recorder.records()] == [("az-0", 0), ("nnue-1", 1), ("az-2", 2), ("nnue-3", 0)]
    assert recorder.first_stepped() is cell._record
    assert idle_before._record.read().metrics == [] == idle_after._record.read().metrics


def test_a_collected_trainer_takes_its_ring(steps):
    import gc
    import weakref

    recorder, registry = steps
    gone, batch = make("nnue")
    kept, _ = make("nnue")
    state, metrics = gone.step(gone.init(0), batch)
    kept.step(kept.init(0), batch)
    ring = weakref.ref(gone._record)
    assert recorder.first_stepped() is gone._record
    assert {dict(key)["trainer"] for key in series(registry, "fishnet_train_step")} == {"nnue-0", "nnue-1"}
    del gone, state, metrics
    gc.collect()  # the trainer and its jitted methods are a cycle
    assert ring() is None
    assert [r.trainer for r in recorder.records()] == ["nnue-1"] and recorder.first_stepped() is kept._record
    assert {dict(key)["trainer"] for key in series(registry, "fishnet_train_step")} == {"nnue-1"}


@pytest.mark.parametrize("kind", ["nnue", "az", "trunk", "share", "latent", "pattern", "kda"])
def test_collector_serves_each_scalar_of_the_latest_step(kind, steps):
    """``fishnet_train_step{trainer,key}`` for exactly the keys the kind's
    step returns, and ``fishnet_train_steps_total{trainer}``: every counter
    doc/observability.md documents for a step is a series."""
    recorder, registry = steps
    trainer, batch = make(kind)
    label = ("trainer", trainer._record.trainer)
    assert series(registry, "fishnet_train_step") == {} and series(registry, "fishnet_train_steps_total") == {(label,): 0.0}
    state = trainer.init(0)
    for _ in range(2):
        state, metrics = trainer.step(state, batch)
    assert series(registry, "fishnet_train_steps_total") == {(label,): 2.0}
    served = series(registry, "fishnet_train_step")
    assert {dict(key)["key"] for key in served} == STEP_KEYS[kind] == set(metrics)
    assert served == {(("key", key), label): float(value) for key, value in metrics.items()}  # the latest step's
    text = registry.render_prometheus()
    assert f'fishnet_train_step{{key="loss",trainer="{label[1]}"}} ' in text and "# TYPE fishnet_train_steps_total counter" in text


def test_an_index_outside_its_block_shows_through_the_series(steps):
    _recorder, registry = steps
    trainer, batch = make("nnue")
    misses = lambda: series(registry, "fishnet_train_step")[(("key", "ft_block_misses"), ("trainer", "nnue-0"))]
    state, _ = trainer.step(trainer.init(0), batch)
    assert misses() == 0.0
    stray = dict(batch, indices=batch["indices"].copy())
    block = stray["indices"][0, 0, 0] // NNUE.block_rows
    stray["indices"][0, 0, 1] = ((block + 1) % 4) * NNUE.block_rows  # an active index in another block than its pair's
    stray["indices"][2, 1, 3] = stray["indices"][0, 0, 1] if stray["indices"][2, 1, 0] // NNUE.block_rows != (block + 1) % 4 else block * NNUE.block_rows
    state, _ = trainer.step(state, stray)
    assert misses() >= 1.0
    state, _ = trainer.step(state, batch)
    assert misses() == 0.0  # a gauge of the latest step, not a sum
    assert [step["ft_block_misses"] > 0 for step in trainer._record.read().metrics] == [False, True, False]


@pytest.mark.parametrize("kind", ["nnue", "az"])
def test_the_recorded_step_runs_the_bare_step_program(kind, steps):
    """Host-only: what ``.step`` hands to the compiler through the recorder
    (the first call inside its span) is the program a bare
    ``jax.jit(trainer._step)`` lowers, and it is traced once."""
    trainer, batch = make(kind)
    jitted, lowered = trainer._step_jit, []

    def spy(state, batch):
        lowered.append(jitted.lower(state, batch))
        return jitted(state, batch)

    trainer._step_jit = spy
    state = trainer.init(0)
    for _ in range(2):
        state, _metrics = trainer.step(state, batch)
    bare, _ = make(kind)
    with persistent_cache(None):
        through = [scopes.without_metadata(low.compile().as_text()) for low in lowered]
        parent_form = jax.jit(bare._step, donate_argnums=(0,)).lower(jax.eval_shape(bare._init, jax.random.PRNGKey(0)), batch)
        assert through[0] == through[1] == scopes.without_metadata(parent_form.compile().as_text())
    assert jitted._cache_size() == 1


def test_a_reader_beside_the_stepping_thread_sees_whole_steps():
    """One thread stores steps as fast as it can while readers copy the
    ring: every reading is a run of consecutive steps, newest last, never
    more than the ring holds, and each step's values belong together."""
    import sys
    import threading

    record = step_metrics.StepRecord("az", 0)
    stop, failures = threading.Event(), []

    def read_until_stopped():
        try:
            while not stop.is_set():
                reading = record.read()
                numbers = [step["n"] for step in reading.metrics]
                assert len(numbers) <= step_metrics.RING_STEPS
                assert numbers == list(range(reading.first, reading.first + len(numbers)))
                assert reading.steps == reading.first + len(numbers) <= record.steps + 1  # the count follows the store
                assert all(step["twice"] == 2 * step["n"] for step in reading.metrics)
        except BaseException as err:
            failures.append(err)

    readers = [threading.Thread(target=read_until_stopped) for _ in range(2 * (os.cpu_count() or 4))]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        for reader in readers:
            reader.start()
        deadline = time.monotonic() + 2.0
        while time.monotonic() < deadline:
            n = record.steps
            record.run(lambda state, batch: (state, {"n": n, "twice": 2 * n}), None, None)
    finally:
        stop.set()
        sys.setswitchinterval(interval)
        for reader in readers:
            reader.join(timeout=60)
    assert not failures, failures[:1]
    assert not any(reader.is_alive() for reader in readers) and record.steps > step_metrics.RING_STEPS
