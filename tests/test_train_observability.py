"""The learner names its own work (doc/observability.md "Training and
compilation"): named scopes through both step programs, the compile
recorder that ``compile_cache.configure()`` installs with its one span a
program brought up, and the start-up spans. CPU, toy widths."""

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
from fishnet_tpu.telemetry.spans import EVENT_STAGES, RECORDER, SpanRecorder
from fishnet_tpu.train import step_metrics
from fishnet_tpu.train.az_trainer import AzTrainer
from fishnet_tpu.train.data import block_noise
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
    # the eighth's: the first kind's scopes and no other, on a sliding layer and on the full one alike (a layer kind's table is an operand, no scope)
    "mellum": ("embed", "layer00.attention", "layer00.router", "layer00.dispatch", "layer00.experts", "layer00.combine",
               "layer01.attention", "layer01.router", "layer01.dispatch", "layer01.experts", "layer01.combine", "final_norm", "policy_head", "value_head"),
    # the ninth's: the eighth's scopes on both copies of a board (the embedding's holds the mask embedding) and ``denoise``: the denoiser's logits under
    # ``forward``, the third term under ``loss``
    "sdar": ("embed", "layer00.attention", "layer00.router", "layer00.dispatch", "layer00.experts", "layer00.combine",
             "layer01.attention", "layer01.router", "layer01.dispatch", "layer01.experts", "layer01.combine", "final_norm", "denoise", "policy_head", "value_head"),
    # the tenth's: no routed layer's scopes at all; a layer's names cover its passes (the pass is no level of a name) and ``exit_gate`` stands beside the heads
    "ouro": ("embed", "layer00.attention", "layer00.dense", "layer01.attention", "layer01.dense", "final_norm", "exit_gate", "policy_head", "value_head"),
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
# the eighth block: a sliding layer under the plain table, then a full layer under YaRN's; 4 of 8 experts held, no shared expert
MELLUM = TrunkConfig(hidden=32, heads=4, kv_heads=1, head_dim=16, layers=2, experts=8, experts_per_token=2, expert_width=16, value_hidden=8, route_norm=True,
                     held_experts=(2, 4), balance_rate=0.001, sliding_window=1024, rope_theta=1e4, full_attention_layers=(1,), rope_type="yarn", rope_factor=16.0,
                     original_max_position_embeddings=2048, attention_factor=1.2772588722239782)
# the ninth block: the eighth's layer under one plain table, trained by block diffusion (a batch carries its noise)
SDAR = TrunkConfig(hidden=32, heads=4, kv_heads=1, head_dim=16, layers=2, experts=8, experts_per_token=2, expert_width=16, value_hidden=8, route_norm=True,
                   held_experts=(2, 4), balance_rate=0.001, rope_theta=1e6, rms_eps=1e-6, block_length=4)
# the tenth block: two layers of attention (a group of one, no qk-norm) and a dense feed-forward between sandwich norms, walked three times, an exit a pass
OURO = TrunkConfig(hidden=32, heads=2, head_dim=16, layers=2, rope_theta=1e6, rms_eps=1e-6, value_hidden=8, qk_norm=False, post_norms=True, dense_layers=2,
                   dense_width=48, loop_steps=3)
TRUNKS = {"trunk": TRUNK, "share": SHARE, "latent": LATENT, "pattern": PATTERN, "cca": CCA, "kda": KDA, "gdn": GDN, "mellum": MELLUM, "sdar": SDAR, "ouro": OURO}


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
        if kind == "sdar":  # a block-diffusion trunk's batch carries its noise (train/data.py block_noise)
            batch["block_level"], batch["square_masked"] = block_noise(np.random.default_rng(0), B, SDAR.block_length, 0.05)
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


@pytest.mark.parametrize("kind", ["share", "latent", "cca", "kda", "gdn", "mellum", "sdar", "ouro"])
def test_a_blocks_own_scopes_are_exactly_the_parents(kind):
    """The scopes of the trunk's own parts in the configurations the
    fixture above does not compile, as PR 46's PARENT (3036d35) named them
    (the sixth block's as PR 47 brought them, the seventh's as PR 51, the eighth's as PR 56, the ninth's as PR 59, the tenth's as PR 64), no more and no fewer: the
    benchmark's reducers read these names, and a lowered step's text (the
    step pins) carries none of them."""
    names = set(re.findall(r'op_name="([^"]*)"', step_text(kind)))
    held = {scope for name in names for part in scopes._parts(name) for scope in [scopes._unwrap(part)[1]]}
    own = {scope for scope in held if re.match(r"layer\d\d\.|embed$|final_norm$|denoise$|exit_gate$", scope)}
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


def near(expected, within=1e-4):
    """JAX's clock is ``time.time()``: a float of today's date resolves 0.2 us, and 600 intervals add that up."""
    return pytest.approx(expected, abs=within)


def own_recorder():
    """A compile recorder on a registry and a span recorder of its own."""
    registry, spans = MetricsRegistry(), SpanRecorder()
    return compile_cache.CompileRecorder(registry, spans), registry, spans


def program_ups(spans):
    return [span for span in spans.spans() if span["stage"] == "program_up"]


def bring_up(recorder, name, at, trace_s, lower_s, backend_s, cache=None, load_s=0.0, nested=()):
    """The events of one program as JAX 0.9.0 sends them (``ev.py``-style
    log of a ``jax.jit``): every nested trace's time span, the program's
    own, its lowering's under ``jit(<name>)``, then the cache's events and
    the time span of ``backend_compile`` that holds them. ``nested`` are
    ``(function, offset into the trace, seconds)``. Returns the end."""
    for fun_name, offset, seconds in nested:
        recorder.on_span(TRACE, at + offset, at + offset + seconds, fun_name=fun_name)
    recorder.on_span(TRACE, at, at + trace_s, fun_name=name)
    lowered = at + trace_s + 0.001
    recorder.on_span(LOWER, lowered, lowered + lower_s, fun_name=f"jit({name})")
    compiled = lowered + lower_s + 0.001
    recorder.on_event("/jax/compilation_cache/compile_requests_use_cache")  # not ours
    if cache == HIT:
        recorder.on_event(HIT)
        recorder.on_duration("/jax/compilation_cache/compile_time_saved_sec", 9.0)  # not ours
        recorder.on_duration(LOAD, load_s)
    elif cache == MISS:
        recorder.on_event(MISS)
    recorder.on_duration(BACKEND, backend_s, fun_name=f"jit({name})")  # JAX sends the duration too: it is the span's
    recorder.on_span(BACKEND, compiled, compiled + backend_s, fun_name=f"jit({name})")
    return compiled + backend_s


def test_configure_twice_installs_one_listener():
    compile_cache.configure()
    recorder = compile_cache.RECORDER
    compile_cache.configure()
    assert compile_cache.configure_recorder() is recorder is compile_cache.RECORDER
    compiles = telemetry.REGISTRY.counter("fishnet_compiles_total", "", labelnames=("cache",))
    seconds = telemetry.REGISTRY.counter("fishnet_compile_seconds_total", "", labelnames=("phase",))
    before = compiles.value(cache="hit"), seconds.value(phase="lower"), recorder.mark()
    now = time.time()
    jax.monitoring.record_event(HIT)  # one listener: counted once
    jax.monitoring.record_event_time_span(LOWER, now - 0.5, now, fun_name="jit(nothing)")
    jax.monitoring.record_event_duration_secs(LOWER, 0.5, fun_name="jit(nothing)")  # the same lowering: JAX sends both
    assert compiles.value(cache="hit") == before[0] + 1
    assert seconds.value(phase="lower") == near(before[1] + 0.5)
    assert recorder.totals_since(before[2])["trace_lower_s"] == near(0.5)
    jax.monitoring.record_event_time_span(BACKEND, now, now, fun_name="jit(nothing)")  # leave no program half brought up


def test_a_miss_then_a_hit_against_a_fresh_cache_directory(tmp_path, monkeypatch):
    """A real cold and warm ``jax.jit`` on the CPU, under the test's own
    cache directory: the counters, the thread's totals, and one
    ``program_up`` each, ``cache: miss`` then ``hit``."""
    compile_cache.configure()
    monkeypatch.setattr(compile_cache, "SMALL_PROGRAM_S", 0.0)  # a miss is a span whatever it took; this hit takes ~5 ms
    hits = lambda: telemetry.REGISTRY.counter("fishnet_compiles_total", "", labelnames=("cache",))
    seconds = lambda: telemetry.REGISTRY.counter("fishnet_compile_seconds_total", "", labelnames=("phase",))
    mine = lambda: [span for span in program_ups(RECORDER) if span["name"] == "warm_and_cold"]
    with persistent_cache(str(tmp_path)):
        def warm_and_cold(x):
            return jax.numpy.tanh(x) * 3.25 + 0.125

        x = jax.numpy.ones((7,), jax.numpy.float32)
        x.block_until_ready()  # its own programs compile before the counts are read
        counts = {k: hits().value(cache=k) for k in ("hit", "miss")}
        mark = compile_cache.RECORDER.mark()
        jax.jit(warm_and_cold)(x).block_until_ready()
        assert hits().value(cache="miss") == counts["miss"] + 1
        assert hits().value(cache="hit") == counts["hit"]
        cold = compile_cache.RECORDER.totals_since(mark)
        assert cold["cache_misses"] == 1 and cold["compile_s"] > 0 and cold["cache_load_s"] == 0
        assert cold["trace_lower_s"] > 0

        jax.clear_caches()
        loaded = seconds().value(phase="cache_load")
        mark = compile_cache.RECORDER.mark()
        jax.jit(warm_and_cold)(x).block_until_ready()
        assert hits().value(cache="hit") == counts["hit"] + 1
        assert hits().value(cache="miss") == counts["miss"] + 1
        assert seconds().value(phase="cache_load") > loaded
        warm = compile_cache.RECORDER.totals_since(mark)
        assert warm["cache_misses"] == 0 and warm["cache_load_s"] > 0
    first, second = mine()
    assert (first["cache"], second["cache"]) == ("miss", "hit")
    assert first["compile_s"] == cold["compile_s"] and first["trace_s"] + first["lower_s"] == near(cold["trace_lower_s"], within=2e-6)
    assert second["cache_load_s"] == warm["cache_load_s"] and second["compile_s"] == warm["compile_s"]
    assert {row[0] for row in first["traced"]} >= {"warm_and_cold", "tanh"}
    assert "parent_id" not in first  # no start-up span was open


def test_recorder_counts_disjoint_phases(monkeypatch):
    """Fed the events of one cold and one warm program (names and order as
    JAX 0.9.0 sends them): nested traces count once, a load is not a
    compile, and the thread's totals are what the kept events used to give."""
    recorder, registry, spans = own_recorder()
    mark = recorder.mark()
    at = time.time()
    nested = [("relu", 0.003 * i, 0.001) for i in range(600)]  # one after another inside _step's own 2 s
    at = bring_up(recorder, "_step", at, trace_s=2.0, lower_s=0.5, backend_s=30.0, cache=MISS, nested=nested)
    bring_up(recorder, "_step", at, trace_s=1.0, lower_s=0.25, backend_s=1.75, cache=HIT, load_s=1.5)
    seconds = registry.counter("fishnet_compile_seconds_total", "", labelnames=("phase",))
    assert seconds.value(phase="trace") == near(3.0)
    assert seconds.value(phase="lower") == near(0.75)
    assert seconds.value(phase="backend") == near(30.25)
    assert seconds.value(phase="cache_load") == near(1.5)
    compiles = registry.counter("fishnet_compiles_total", "", labelnames=("cache",))
    assert (compiles.value(cache="miss"), compiles.value(cache="hit")) == (1, 1)
    assert recorder.totals_since(mark) == {
        "compile_s": near(30.25), "cache_load_s": 1.5, "trace_lower_s": near(3.75), "cache_misses": 1}
    cold, warm = program_ups(spans)
    assert cold["traced"] == [["_step", 1, near(1.4)], ["relu", 600, near(0.6)]]  # ONE row of 600 calls
    assert (warm["trace_s"], warm["lower_s"], warm["cache_load_s"], warm["compile_s"]) == near((1.0, 0.25, 1.5, 0.25))


def test_one_program_is_one_record_with_its_name_phases_and_cache():
    recorder, _registry, spans = own_recorder()
    at = time.time()
    ended = bring_up(recorder, "balanced_bias", at, trace_s=0.4, lower_s=0.2, backend_s=1.3, cache=HIT, load_s=0.9)
    (span,) = spans.spans()
    assert (span["stage"], span["name"], span["cache"]) == ("program_up", "balanced_bias", "hit")
    assert (span["trace_s"], span["lower_s"], span["cache_load_s"], span["compile_s"]) == near((0.4, 0.2, 0.9, 0.4))
    # its start and its duration are the program's own, on the span recorder's clock
    assert span["t"] == near(at - spans.epoch_offset, within=1e-5) and span["dur_ms"] == near(1e3 * (ended - at), within=1e-2)
    assert span["traced"] == [["balanced_bias", 1, near(0.4)]] and span["small"] == [0, 0.0]
    bring_up(recorder, "uncached", ended, trace_s=0.1, lower_s=0.1, backend_s=0.1)  # no cache asked: no cache event
    assert [(s["name"], s["cache"], s["compile_s"]) for s in program_ups(spans)][1] == ("uncached", "none", near(0.1))


def test_same_named_nested_traces_add_up():
    recorder, _registry, spans = own_recorder()
    nested = [("wrapped", 0.1, 0.25), ("wrapped", 0.5, 0.5), ("dot", 1.2, 0.125)]
    bring_up(recorder, "_step", time.time(), trace_s=2.0, lower_s=0.1, backend_s=0.1, nested=nested)
    (span,) = program_ups(spans)
    assert span["traced"] == [["_step", 1, near(1.125)], ["wrapped", 2, near(0.75)], ["dot", 1, near(0.125)]]
    assert span["trace_s"] == near(2.0)  # the outermost trace alone: what it holds is inside it


def test_self_time_is_the_interval_less_what_is_nested_in_it():
    """A parent of 1.0 s holding children of 0.3 and 0.2 reads 0.5; a
    grandchild comes off its parent, not off the root."""
    recorder, _registry, spans = own_recorder()
    nested = [("leaf", 0.15, 0.1), ("child_a", 0.1, 0.3), ("child_b", 0.6, 0.2)]  # as they end: leaf lies inside child_a
    bring_up(recorder, "parent", time.time(), trace_s=1.0, lower_s=0.1, backend_s=0.1, nested=nested)
    (span,) = program_ups(spans)
    assert {name: (calls, self_s) for name, calls, self_s in span["traced"]} == {
        "parent": (1, near(0.5)), "child_a": (1, near(0.2)), "child_b": (1, near(0.2)), "leaf": (1, near(0.1))}
    assert len(span["traced"]) <= compile_cache.TRACED_KEPT
    many = [(f"f{i}", 0.01 * i, 0.001 * (i + 1)) for i in range(20)]
    bring_up(recorder, "wide", time.time(), trace_s=1.0, lower_s=0.1, backend_s=0.1, nested=many)
    kept = program_ups(spans)[1]["traced"]
    assert [row[0] for row in kept] == ["wide"] + [f"f{i}" for i in range(19, 12, -1)]  # the eight that cost most, costliest first


def test_a_program_lowered_under_another_name_takes_the_outermost_trace():
    """Not the longest by seconds: a stray trace that led to no program
    (``jax.eval_shape``) may be longer than the program's own. What the
    lowering itself traced (a kernel's body) is the program's too."""
    recorder, registry, spans = own_recorder()
    at = time.time()
    recorder.on_span(TRACE, at, at + 5.0, fun_name="_init")  # eval_shape: no lowering follows
    recorder.on_span(TRACE, at + 6.1, at + 6.3, fun_name="inner")
    recorder.on_span(TRACE, at + 6.0, at + 7.0, fun_name="call_wrapped")
    recorder.on_span(TRACE, at + 7.2, at + 7.3, fun_name="kernel_body")  # inside the lowering
    recorder.on_span(LOWER, at + 7.1, at + 7.5, fun_name="pmap(step)")
    recorder.on_span(BACKEND, at + 7.6, at + 7.7, fun_name="pmap(step)")
    (span,) = program_ups(spans)
    assert (span["name"], span["trace_s"]) == ("pmap(step)", near(1.0))
    assert registry.counter("fishnet_compile_seconds_total", "", labelnames=("phase",)).value(phase="trace") == near(1.0)
    assert [row[0] for row in span["traced"]] == ["call_wrapped", "inner", "kernel_body"]
    assert span["t"] == near(at + 6.0 - spans.epoch_offset, within=1e-5)
    # the stray was forgotten at the lowering: the next program does not find it
    bring_up(recorder, "next", at + 8.0, trace_s=0.1, lower_s=0.1, backend_s=0.1)
    assert [row[0] for row in program_ups(spans)[1]["traced"]] == ["next"]


def test_a_lowering_that_is_never_compiled_is_recorded_at_the_next():
    recorder, _registry, spans = own_recorder()
    at = time.time()
    recorder.on_span(TRACE, at, at + 0.5, fun_name="_step")
    recorder.on_span(LOWER, at + 0.5, at + 1.0, fun_name="jit(_step)")  # ``.lower()`` alone
    assert program_ups(spans) == []
    bring_up(recorder, "other", at + 2.0, trace_s=0.1, lower_s=0.1, backend_s=0.1, cache=MISS)
    first, second = program_ups(spans)
    assert (first["name"], first["cache"], first["compile_s"], first["dur_ms"]) == ("_step", "none", 0.0, near(1000.0, within=1e-2))
    assert (second["name"], second["cache"]) == ("other", "miss")
    # a backend compile with no lowering on its thread (lowered elsewhere) is a program of that phase alone
    recorder.on_span(BACKEND, at + 3.0, at + 3.5, fun_name="jit(elsewhere)")
    last = program_ups(spans)[2]
    assert (last["name"], last["trace_s"], last["lower_s"], last["compile_s"], last["traced"]) == ("elsewhere", 0.0, 0.0, near(0.5), [])


def test_events_of_two_threads_interleaved_do_not_mix():
    import threading

    recorder, _registry, spans = own_recorder()
    at = time.time()
    turns = [threading.Semaphore(0), threading.Semaphore(0)]
    totals = {}

    def bring(me, name, scale):
        """One event a turn, the other thread's next event between each two of mine."""
        events = [
            lambda: recorder.on_span(TRACE, at + 0.1, at + 0.1 + 0.2 * scale, fun_name="inner_" + name),
            lambda: recorder.on_span(TRACE, at, at + 1.0 * scale, fun_name=name),
            lambda: recorder.on_span(LOWER, at + 2.0, at + 2.0 + 0.5 * scale, fun_name=f"jit({name})"),
            lambda: recorder.on_event(MISS if me else HIT),
            lambda: recorder.on_duration(LOAD, 0.0 if me else 0.25),
            lambda: recorder.on_span(BACKEND, at + 3.0, at + 3.0 + 1.0 * scale, fun_name=f"jit({name})"),
        ]
        mark = recorder.mark()
        for event in events:
            assert turns[me].acquire(timeout=30)
            event()
            turns[1 - me].release()
        totals[name] = recorder.totals_since(mark)

    threads = [threading.Thread(target=bring, args=(0, "first", 1.0), name="bringer-0"),
               threading.Thread(target=bring, args=(1, "second", 2.0), name="bringer-1")]
    for thread in threads:
        thread.start()
    turns[0].release()
    for thread in threads:
        thread.join(timeout=60)
        assert not thread.is_alive()
    first, second = sorted(program_ups(spans), key=lambda span: span["name"])
    assert (first["thread"], first["cache"], first["trace_s"], first["cache_load_s"], first["compile_s"]) == (
        "bringer-0", "hit", near(1.0), 0.25, near(0.75))
    assert (second["thread"], second["cache"], second["trace_s"], second["cache_load_s"], second["compile_s"]) == (
        "bringer-1", "miss", near(2.0), 0.0, near(2.0))
    assert [row[0] for row in first["traced"]] == ["first", "inner_first"] and [row[0] for row in second["traced"]] == ["second", "inner_second"]
    assert totals["first"] == {"compile_s": near(0.75), "cache_load_s": 0.25, "trace_lower_s": near(1.5), "cache_misses": 0}
    assert totals["second"] == {"compile_s": near(2.0), "cache_load_s": 0.0, "trace_lower_s": near(3.0), "cache_misses": 1}


def test_a_program_under_ten_milliseconds_is_counted_in_small_and_is_no_span():
    recorder, registry, spans = own_recorder()
    at = time.time()
    for i in range(1000):  # a thousand eager one-op programs flood no ring
        at = bring_up(recorder, "add", at, trace_s=0.001, lower_s=0.002, backend_s=0.004, cache=HIT if i % 2 else None, load_s=0.003)
    assert program_ups(spans) == []
    count, seconds = recorder.small()
    assert count == 1000 and seconds == near(500 * 0.007 + 500 * 0.007)
    assert registry.counter("fishnet_compile_seconds_total", "", labelnames=("phase",)).value(phase="trace") == near(1.0)  # counted all the same
    bring_up(recorder, "add", at, trace_s=0.001, lower_s=0.002, backend_s=0.004, cache=MISS)  # a miss is a span whatever it took
    bring_up(recorder, "slow_add", at + 1.0, trace_s=0.004, lower_s=0.004, backend_s=0.004)
    missed, slow = program_ups(spans)
    assert (missed["name"], missed["cache"], slow["name"], slow["cache"]) == ("add", "miss", "slow_add", "none")
    assert missed["small"] == slow["small"] == [1000, near(7.0)] == recorder.small()  # as of each span's end


def test_a_program_inside_a_startup_span_is_its_child(monkeypatch):
    """``program_up`` inside an open ``train_init`` carries its ``span_id``
    as ``parent_id``; one outside carries none. The start-up span's self
    time is then its duration less its children's."""
    from fishnet_tpu.train import startup

    recorder, _registry, spans = own_recorder()
    monkeypatch.setattr(compile_cache, "RECORDER", recorder)  # what configure_recorder() hands startup.py
    monkeypatch.setattr(startup, "RECORDER", spans)
    bring_up(recorder, "before", time.time(), trace_s=0.1, lower_s=0.1, backend_s=0.1)
    with startup.init_span("az", layout_held_leaves=0):
        now = time.time()
        bring_up(recorder, "init", now - 0.5, trace_s=0.1, lower_s=0.1, backend_s=0.2, cache=MISS)
        bring_up(recorder, "tiny", now, trace_s=0.001, lower_s=0.001, backend_s=0.001)
    bring_up(recorder, "after", time.time(), trace_s=0.1, lower_s=0.1, backend_s=0.1)
    by_name = {span.get("name", span["stage"]): span for span in spans.spans()}
    init_span = by_name["train_init"]
    assert by_name["init"]["parent_id"] == init_span["span_id"] and by_name["init"]["trace_id"] == init_span["trace_id"]
    assert by_name["init"]["span_id"] != init_span["span_id"] and "parent_id" not in init_span
    for outside in ("before", "after"):
        assert not {"parent_id", "span_id", "trace_id"} & set(by_name[outside])
    assert (init_span["trainer"], init_span["layout_held_leaves"], init_span["cache_misses"]) == ("az", 0, 1)
    assert (init_span["compile_s"], init_span["trace_lower_s"]) == near((0.201, 0.202))  # the small one's seconds too
    assert init_span["small_at_start"] == [0, 0.0] and init_span["small_at_end"] == [1, near(0.003)]


# -- B. the start-up spans -------------------------------------------------------


def test_the_block_diffusion_trainers_init_span_says_its_heads_go_two_a_product():
    """Since PR 63 the block-masked kernel pair takes a key-value head's query heads two a product as the plain pair does: the ninth block's
    learner (4 heads over 1: two pairs a layer) reads 1.0 on its ``train_init`` span where it read 0.0."""
    started = time.monotonic()
    trainer, _ = make("sdar")
    trainer.init(1)
    span = [s for s in RECORDER.spans() if s["t"] >= started and s["stage"] == "train_init"][-1]
    assert span["trainer"] == "az" and span["attention_heads_paired"] == 1.0


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


def test_process_spans_once_a_process_however_many_trainers(monkeypatch, steps):
    """``process_boot`` runs from the process's start (read off
    ``/proc/self/stat``) to the package's first import, ``program_import``
    from there to the first trainer's construction, and the two meet."""
    import fishnet_tpu
    from fishnet_tpu.train import startup

    spans = SpanRecorder()
    monkeypatch.setattr(startup, "RECORDER", spans)
    monkeypatch.setattr(startup, "_process_spans_pending", True)
    before = time.monotonic()
    for kind in ("az", "nnue", "az"):
        make(kind)
    boot, imported = spans.spans()  # once, whatever was made after
    assert (boot["stage"], imported["stage"]) == ("process_boot", "program_import")
    assert boot["t"] + boot["dur_ms"] / 1e3 == pytest.approx(fishnet_tpu.FIRST_IMPORT, abs=2e-3) == pytest.approx(imported["t"], abs=1e-6)
    assert before <= imported["t"] + imported["dur_ms"] / 1e3 <= time.monotonic()
    assert 0 < boot["dur_ms"] / 1e3 < 3600 and boot["t"] < fishnet_tpu.FIRST_IMPORT  # this process's age, not the machine's
    assert startup.process_started() == pytest.approx(boot["t"], abs=0.011)  # to a clock tick


@pytest.mark.parametrize("stat", [None, "1 (python) S 1 2", "1 (a b) c) S " + " ".join(["0"] * 18) + " 99999999999999 0"])
def test_process_boot_is_absent_not_zero_where_the_start_cannot_be_read(monkeypatch, steps, tmp_path, stat):
    """No ``/proc``, a line too short, a start in the future: no
    ``process_boot``; ``program_import`` needs none of it."""
    import builtins

    from fishnet_tpu.train import startup

    real_open = builtins.open

    def no_proc(path, *args, **kwargs):
        if path != "/proc/self/stat":
            return real_open(path, *args, **kwargs)
        if stat is None:
            raise FileNotFoundError(path)
        (tmp_path / "stat").write_text(stat)
        return real_open(tmp_path / "stat")

    spans = SpanRecorder()
    monkeypatch.setattr(startup, "RECORDER", spans)
    monkeypatch.setattr(startup, "_process_spans_pending", True)
    monkeypatch.setattr(builtins, "open", no_proc)
    assert startup.process_started() is None
    make("nnue")
    assert [span["stage"] for span in spans.spans()] == ["program_import"]


def test_the_exporter_lists_the_new_stages_under_spans():
    import json
    import urllib.request

    from fishnet_tpu.telemetry.exporter import MetricsExporter

    assert {"program_up", "process_boot", "program_import"} <= set(EVENT_STAGES)
    trainer, batch = make("nnue")  # a process that has made and stepped a trainer
    trainer.step(trainer.init(3), batch)
    exporter = MetricsExporter(port=0)
    try:
        with urllib.request.urlopen(f"{exporter.url}/spans", timeout=10) as res:
            served = json.loads(res.read())["spans"]
    finally:
        exporter.close()
    stages = {span["stage"] for span in served}
    assert {"program_import", "train_init", "train_first_step", "program_up"} <= stages
    assert ("process_boot" in stages) == os.path.exists("/proc/self/stat")
    step = next(span for span in reversed(served) if span["stage"] == "program_up" and span["name"] == "_step")
    first_step = next(span for span in reversed(served) if span["stage"] == "train_first_step")
    assert step["parent_id"] == first_step["span_id"] and step["traced"][0][0] == "_step"
    assert {"name", "trace_s", "lower_s", "cache_load_s", "compile_s", "cache", "traced", "small"} <= set(step)


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
    # the ninth block's third term and the two counters of its batch's noise
    "sdar": LOSSES["az"] | ROUTING | {"held_slots", "expert_bias_abs_max", "denoise_loss", "masked_squares", "noise_level_mean"},
    # the tenth block's: no routing counter (no routed layer), the exits' four and the loop's own
    "ouro": LOSSES["az"] | {"exit_step_mean", "exit_entropy", "loss_first_pass", "loss_last_pass", "loop_update_rms"},
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


@pytest.mark.parametrize("kind", ["nnue", "az", "trunk", "share", "latent", "pattern", "kda", "sdar", "ouro"])
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
