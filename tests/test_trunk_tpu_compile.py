"""The trunk's Pallas kernels (grouped product, row moves, attention core)
compiled for a described TPU v5e at the published widths: what Mosaic refuses (a tile that does not fit VMEM, a
slice off the tiling) fails here, on the CPU, at no chip time. Nothing
runs: a compile says nothing about results or times.

The topology is described inside a fixture, never at import: only one
process may load the TPU's library, and every xdist worker imports every
test file. Keep such tests in this one file."""

import math

import jax
import jax.numpy as jnp
import pytest

from fishnet_tpu.models import trunk

SLOTS, HIDDEN, WIDTH, EXPERTS = 262_144, 2048, 1024, 64  # moe_trunk_train_b512: 512 positions x 64 squares x top-8


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as err:  # no libtpu here, or another process holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {err}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def compiled_for_tpu(monkeypatch):
    """The kernel itself, not the interpreter's loops; and no write to the
    persistent cache, which a described device cannot read back."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    monkeypatch.setattr(trunk, "_interpret", lambda: False)
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    cc.reset_cache()


@pytest.mark.parametrize("rows_in,rows_out", [(HIDDEN, WIDTH), (WIDTH, HIDDEN)], ids=["gate_up", "down"])
def test_grouped_matmul_and_its_gradients_compile_at_published_widths(one_chip, compiled_for_tpu, rows_in, rows_out):
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    args = (sds((SLOTS, rows_in), jnp.bfloat16), sds((EXPERTS, rows_in, rows_out), jnp.float32), sds((EXPERTS,), jnp.int32))

    def loss(rows, weights, group_sizes):
        return jnp.sum(trunk.grouped_matmul(rows, weights, group_sizes).astype(jnp.float32))

    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(*args).compile().as_text()
    assert text.count("tpu_custom_call") >= 3  # gmm forward, gmm on the transposed weights, tgmm


def _xla_passes_over_slots(text: str, slots: int):
    """The first instructions of a compiled module's text under a ``layerNN.experts`` scope whose result is ``[slots, .]``
    and that XLA itself computes (neither a kernel nor a view of one's result)."""
    import re

    return [line for line in text.splitlines() if re.search(r"layer\d+\.experts", line) and re.search(rf"= \S*\[{slots},", line)
            and not re.search(r" (custom-call|get-tuple-element|bitcast)\(", line)][:2]


#: slots, experts whose weights are here, expert width: the three trunks' cells (a share is given its held groups' sizes alone).
EXPERT_SHAPES = {"moe_trunk_train_b512": (262_144, 64, 1024), "afmoe_trunk_train_b256": (131_072, 8, 1024), "mla_trunk_train_b256": (98_304, 8, 768)}


@pytest.mark.parametrize("slots,held,width", EXPERT_SHAPES.values(), ids=EXPERT_SHAPES)
def test_the_joined_product_and_the_gate_kernels_compile_at_published_widths(one_chip, compiled_for_tpu, slots, held, width):
    """Gate and up as one product ``[slots, 2048] x [held, 2048, 2 x width]``
    with both gradients, each at its own tiles (1,536 joined columns in
    tiles of 768), and ``expert_gate`` / ``expert_gate_grad`` between it
    and the down product, under a traced extent where a share holds the
    experts and at the static grid where all are held: eight kernels and
    no XLA pass over ``[slots, .]`` between them."""
    import re

    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    share = held < 64
    args = (sds((slots, HIDDEN), jnp.bfloat16), sds((held, HIDDEN, width), jnp.float32), sds((held, HIDDEN, width), jnp.float32),
            sds((held, width, HIDDEN), jnp.float32), sds((held,), jnp.int32), sds((), jnp.int32))

    def loss(rows, gate_w, up_w, down_w, group_sizes, extent):
        with jax.named_scope("layer01.experts"):
            out = trunk._expert_ffn(rows, gate_w, up_w, down_w, group_sizes, extent if share else None)
        return jnp.sum(jnp.where(jnp.arange(slots)[:, None] < extent, out.astype(jnp.float32), 0.0))

    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3))).lower(*args).compile().as_text()
    kernels = [line for line in text.splitlines() if 'custom_call_target="tpu_custom_call"' in line]
    assert len(kernels) == 8, len(kernels)  # forward 2 products + the gate; backward 2 gmm + 2 tgmm + the gate's gradient
    own = [re.match(r"\s*(?:ROOT )?%([\w.\-]+) = ", line).group(1) for line in kernels]  # a line names its operands too
    assert sum("expert_gate_grad" in name for name in own) == 1 and sum("expert_gate" in name for name in own) == 2, own
    assert [line for line in kernels if f"bf16[{slots},{2 * width}]" in line and f"bf16[{held},{HIDDEN},{2 * width}]" in line]  # the joined product
    assert not _xla_passes_over_slots(text, slots)


def _computations(text: str):
    """A compiled module's computations by name, each a list of its instructions as (name, opcode, operand names, the
    computation it calls or None, the types of its results such as ``bf16[16384,4096]``), and the name of the entry computation."""
    import re

    computations, current = {}, None
    for line in text.splitlines():
        header = re.match(r"(?:ENTRY )?%?([\w.\-]+) \(.*\) -> .* \{$", line) if not line.startswith(" ") else None
        instruction = re.match(r"\s+(?:ROOT )?%([\w.\-]+) = (.+?) ([a-z][\w\-]*)\(([^)]*)\)", line)
        if header:
            current = computations.setdefault(header.group(1), [])
        elif current is not None and instruction:
            name, results, opcode, operands = instruction.groups()
            called = re.search(r"calls=%?([\w.\-]+)", line)
            current.append((name, opcode, re.findall(r"%([\w.\-]+)", operands), called and called.group(1), re.findall(r"\w+\[[\d,]*\]", results)))
    return computations, re.search(r"^ENTRY %?([\w.\-]+)", text, re.M).group(1)


def _opcodes_fused_with_a_product(text: str, feeding: bool = False):
    """For each fusion of a compiled module's entry computation that holds a ``convolution`` (its own or a nested fusion's,
    which is how the TPU compiler puts a producer into a product's operand): its name and every opcode it holds, or with
    ``feeding`` the opcodes that feed the convolution alone (transitively its operands inside the fusion, a nested fusion
    whole): what sits in the product's operand, not what reads its result."""
    computations, entry = _computations(text)

    def held(computation):
        opcodes = set()
        for _name, opcode, _operands, called, _results in computations.get(computation, ()):
            opcodes |= held(called) if opcode == "fusion" and called else {opcode}
        return opcodes

    def fed(computation):
        instructions = {name: rest for name, *rest in computations.get(computation, ())}
        opcodes, seen, reached = set(), set(), []
        for opcode, operands, called, _results in instructions.values():
            if opcode == "convolution" or opcode == "fusion" and called and "convolution" in held(called):
                reached += operands
                opcodes |= fed(called) if opcode == "fusion" else set()
        while reached:
            name = reached.pop()
            if name in instructions and name not in seen:
                seen.add(name)
                opcode, operands, called, _results = instructions[name]
                opcodes |= held(called) if opcode == "fusion" and called else {opcode}
                reached += operands
        return opcodes

    return {name: (fed if feeding else held)(called) for name, opcode, _operands, called, _results in computations[entry]
            if opcode == "fusion" and called and "convolution" in held(called)}


DENSE_WIDTH = 6144  # the leading dense layer of afmoe_trunk_train_b256 and mla_trunk_train_b256, on 256 x 64 tokens


def test_the_dense_layers_products_hold_no_activation_gradient(one_chip, compiled_for_tpu):
    """``value_and_grad`` of the dense layer as a step runs it (norm,
    ``_gated_ffn``, residual) at ``[16384, 2048] x 6144``: the kernel pair
    fits VMEM at the row tile its float32 rows take, the six products
    (gate and up joined forward, in the weights' gradient and in the
    input's) are all there are, and none holds an ``exponential`` or a
    ``divide``: until PR 42 XLA made no array of the activation's
    gradient and fused its whole chain into each gradient product's
    operand (nine products, five of them behind an ``exp`` and a
    ``divide`` an operand element)."""
    sds = lambda shape: jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
    layer = {"moe_norm": sds((HIDDEN,)), "dense_gate": sds((HIDDEN, DENSE_WIDTH)), "dense_up": sds((HIDDEN, DENSE_WIDTH)),
             "dense_down": sds((DENSE_WIDTH, HIDDEN))}

    def loss(x, p):
        with jax.named_scope("layer00.dense"):
            return jnp.sum(jnp.square(x + trunk._gated_ffn(trunk._rms_norm(x, p["moe_norm"], 1e-5), p, "dense")))  # a cotangent that waits for the result

    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(sds((AFMOE_BOARDS * trunk.SQUARES, HIDDEN)), layer).compile().as_text()
    kernels = [line.split(" = ")[0].strip() for line in text.splitlines() if 'custom_call_target="tpu_custom_call"' in line]
    assert len(kernels) == 2 and sum("expert_gate_grad" in name for name in kernels) == 1 and all("expert_gate" in name for name in kernels), kernels
    assert f"f32[16384,{2 * DENSE_WIDTH}]" in text and f"bf16[16384,{2 * DENSE_WIDTH}]" in text  # ``gu`` float32 from product to kernel, ``d_gu`` bfloat16
    products = _opcodes_fused_with_a_product(text)
    assert len(products) == 6, sorted(products)
    assert not {name: sorted(opcodes & {"exponential", "divide"}) for name, opcodes in products.items() if opcodes & {"exponential", "divide"}}


TOKENS, TOP_K = 32_768, 8  # SLOTS = TOKENS * TOP_K


def test_row_moves_and_their_gradients_compile_at_published_widths(one_chip, compiled_for_tpu):
    """Both kernels, forward and as each other's gradient: the one-row DMA
    on the row view, the VMEM reshape, the index blocks in SMEM."""
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def loss(tokens, out, weight, order):
        rows = trunk._dispatch(tokens, order)  # rows_out; its gradient rows_back
        mixed = trunk._combine(out, weight, order)  # rows_back; its gradient rows_out with the scale
        return jnp.sum(rows.astype(jnp.float32)) + jnp.sum(mixed)

    args = (sds((TOKENS, HIDDEN), jnp.bfloat16), sds((SLOTS, HIDDEN), jnp.bfloat16),
            sds((TOKENS, TOP_K), jnp.float32), sds((SLOTS,), jnp.int32))
    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2))).lower(*args).compile().as_text()
    assert text.count("moe_rows_out") >= 2 and text.count("moe_rows_back") >= 2


def test_step_text_equal_tells_a_moved_kernel_from_a_changed_one(one_chip, compiled_for_tpu, tmp_path, capsys, monkeypatch):
    """``tools/step_text_equal.py`` on a two-kernel program: traced from
    another line the text differs and the tool finds the programs equal
    (a Mosaic module keeps the lines of its trace's call stack); with
    another row tile it does not. It parses the compiled text with
    ``jax._src``'s MLIR bindings: this is where an upgrade that breaks it
    shows."""
    import importlib.util
    import math
    from pathlib import Path

    from fishnet_tpu.ops import row_move

    spec = importlib.util.spec_from_file_location("step_text_equal", Path(__file__).resolve().parent.parent / "tools" / "step_text_equal.py")
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    args = (jax.ShapeDtypeStruct((2048, HIDDEN), jnp.bfloat16, sharding=one_chip), jax.ShapeDtypeStruct((2048,), jnp.int32, sharding=one_chip))

    def moves(rows, index):
        return row_move.rows_back(row_move.rows_out(row_move.row_view(rows), index), index)

    def again(f):  # a function of its own every time (``jit`` keeps what it traced), named alike (a module is named after its function)
        g = lambda rows, index: f(rows, index)
        g.__name__ = "moves"
        return g

    def text(f, name):
        (tmp_path / name).write_text(jax.jit(f).lower(*args).compile().as_text())
        return str(tmp_path / name)

    here, there = text(again(moves), "here"), text(again(again(moves)), "there")  # the same program, traced through one more frame
    assert tool.main([here, there]) == 0 and "text without metadata: differs" in capsys.readouterr().out
    monkeypatch.setattr(row_move, "_tile", lambda rows, most=256: math.gcd(rows, most))  # ``rows_back`` takes 512 rows a grid step otherwise
    assert tool.main([here, text(again(moves), "other")]) == 1 and "['moe_rows_back.1']" in capsys.readouterr().out


def test_experts_step_at_published_widths_gathers_no_slot_rows(one_chip, compiled_for_tpu):
    """``value_and_grad`` of ``_experts`` as the cell runs it: no XLA
    gather produces the 1 GiB ``bf16[262144,2048]`` any more."""
    import re

    cfg = trunk.TrunkConfig()
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    layer = {"router_w": sds((HIDDEN, EXPERTS), jnp.float32),
             "experts_gate": sds((EXPERTS, HIDDEN, WIDTH), jnp.float32),
             "experts_up": sds((EXPERTS, HIDDEN, WIDTH), jnp.float32),
             "experts_down": sds((EXPERTS, WIDTH, HIDDEN), jnp.float32)}

    def loss(n2, p):
        return jnp.sum(trunk._experts(n2, p, cfg, "layer00")[0])

    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(sds((TOKENS, HIDDEN), jnp.float32), layer).compile().as_text()
    slot_gathers = [line for line in text.splitlines() if re.search(r"= bf16\[262144,2048\]\S* gather\(", line)]
    assert not slot_gathers, slot_gathers[:2]
    assert text.count("moe_rows_out") >= 2 and text.count("moe_rows_back") >= 2


BOARDS = 512  # moe_trunk_train_b512


def _sublayer_shapes(cfg, sublayer, sharding):
    """A sublayer's own tensors as float32 shapes on ``sharding``: ``trunk.sublayer_params`` of the stacked shapes."""
    stacked = {name: jax.ShapeDtypeStruct(shape, jnp.float32) for name, shape in trunk.trunk_param_shapes(cfg).items()}
    sliced = jax.eval_shape(lambda params: trunk.sublayer_params(params, sublayer), stacked)
    return {name: jax.ShapeDtypeStruct(leaf.shape, jnp.float32, sharding=sharding) for name, leaf in sliced.items()}


def test_attention_at_published_widths_is_two_kernels_and_keeps_no_scores(one_chip, compiled_for_tpu):
    """``value_and_grad`` of ``_attention`` as the cell runs it, 16 heads x
    128 over 512 boards' tokens: the core is the two Pallas kernels, the
    64 x 64 scores (or a pair of heads' 64 x 128, the form the grouped
    layers' kernels make since PR 61) never exist as an array, and no copy
    changes ``[512, 64, 2048]`` into a ``[.., 16, 128]`` view."""
    import re

    cfg = trunk.TrunkConfig()
    inner = cfg.heads * cfg.head_dim
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    layer = {"attn_norm": sds((HIDDEN,), jnp.float32), "q_norm": sds((cfg.head_dim,), jnp.float32), "k_norm": sds((cfg.head_dim,), jnp.float32),
             "wq": sds((HIDDEN, inner), jnp.float32), "wk": sds((HIDDEN, inner), jnp.float32),
             "wv": sds((HIDDEN, inner), jnp.float32), "wo": sds((inner, HIDDEN), jnp.float32)}

    def loss(x, p):
        return jnp.sum(trunk._attention(x, p, cfg, trunk.trunk_plan(cfg)[0])[0])

    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(sds((BOARDS * trunk.SQUARES, HIDDEN), jnp.float32), layer).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    assert "board_attention" in text and "board_attention_grad" in text
    per_head = [line for line in text.splitlines() if re.search(r"\[512,16,64,64\]|\[512,8,64,128\]|\[512,64,16,128\]|\[512,16,64,128\]", line)]
    assert not per_head, per_head[:2]


# -- the second block at the shapes of afmoe_trunk_train_b256: 256 boards, 32 query heads over 4 key-value heads, -----
# -- 131,072 slots a routed layer of which 8 of 128 experts are held -------------------------------------------------

AFMOE = trunk.TrunkConfig(heads=32, kv_heads=4, experts=128, held_experts=(0, 8), gated_attention=True, post_norms=True,
                          router_score="sigmoid", route_norm=True, route_scale=2.826, shared_width=1024, balance_rate=0.001,
                          rope_theta=10000.0)
AFMOE_BOARDS = 256


@pytest.mark.parametrize("rope", [True, False], ids=["rope", "nope"])
def test_grouped_query_attention_compiles_at_published_widths(one_chip, compiled_for_tpu, rope):
    """``value_and_grad`` of ``_attention`` with 8 query heads a key-value
    head, the output gate and the post-norm, with and without RoPE: still
    the two kernels, a grid step's blocks (4 boards of a key-value head and
    its 8 query heads since PR 61) inside the 16 MiB a kernel gets, and
    neither a head's ``[64, 64]`` scores nor a pair's ``[64, 128]`` in HBM."""
    import re

    cfg, inner, kv_inner = AFMOE, 32 * 128, 4 * 128
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    layer = {"attn_norm": sds((HIDDEN,), jnp.float32), "post_attn_norm": sds((HIDDEN,), jnp.float32),
             "q_norm": sds((cfg.head_dim,), jnp.float32), "k_norm": sds((cfg.head_dim,), jnp.float32),
             "wq": sds((HIDDEN, inner), jnp.float32), "wgate": sds((HIDDEN, inner), jnp.float32),
             "wk": sds((HIDDEN, kv_inner), jnp.float32), "wv": sds((HIDDEN, kv_inner), jnp.float32), "wo": sds((inner, HIDDEN), jnp.float32)}

    sublayer = trunk.trunk_plan(cfg)[0]._replace(rope=rope)
    assert sublayer.kind == "attention" and sublayer.post_norm == "post_attn_norm"

    def loss(x, p):  # the branch and its post-norm, as the trunk's loop adds them
        return jnp.sum(trunk._rms_norm(trunk._attention(x, p, cfg, sublayer)[0], p[sublayer.post_norm], cfg.rms_eps))

    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(sds((AFMOE_BOARDS * trunk.SQUARES, HIDDEN), jnp.float32), layer).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    assert "board_attention" in text and "board_attention_grad" in text
    scores = [line for line in text.splitlines() if re.search(r"\[256,32,64,64\]|\[256,16,64,128\]|\[256,64,32,128\]|\[256,32,64,128\]", line)]
    assert not scores, scores[:2]


def _products_results_and_operands(text: str):
    """For each fusion of the entry computation that holds a ``convolution``: the types of its results, and of each operand
    its type and the opcode it comes from (seen through the entry's bitcasts and tuple elements)."""
    computations, entry = _computations(text)
    by_name = {name: rest for name, *rest in computations[entry]}

    def origin(name):
        opcode, operands, _called, _results = by_name[name]
        return origin(operands[0]) if opcode in ("bitcast", "get-tuple-element") else opcode

    return {name: (by_name[name][3], [(t, origin(operand)) for operand in by_name[name][1] for t in by_name[operand][3][:1]])
            for name in _opcodes_fused_with_a_product(text)}


def test_the_gated_out_projections_products_read_and_write_arrays(one_chip, compiled_for_tpu):
    """``value_and_grad`` of ``_attention``, its post-norm and the residual
    as the loop adds them, 256 boards of ``AFMOE``: since PR 50 the gate
    and ``W_o`` are one ``custom_vjp`` (``trunk._gated_out``) whose
    products read arrays. The fifteen products (five projections, three
    passes each) are all there are; NOTHING that feeds a product holds an
    ``exponential`` or a ``divide`` (the one fusion of a product that holds
    them is the gate's forward product, whose RESULT the sigmoid reads:
    read on the chip at 1.48 ms against 1.43 alone, PERF.md section 6,
    PR 50); the only float32 ``[16384, 4096]`` a product reads is the
    gradient kernel's ``dq``, in ``W_q``'s two gradient products (the
    gate's logits go from their product to a pass, ``mixed`` is read
    bfloat16 as the kernel wrote it); and the one product that writes
    bfloat16 ``[16384, 4096]`` alone, ``W_o``'s input gradient, has that
    ONE result. The parent left the branch to autodiff: four products read
    a float32 ``[16384, 4096]`` that no kernel wrote (the gate's forward
    product ``mixed`` as a float32 array made for it, ``W_o``'s input
    gradient the sigmoid, ``W_gate``'s both and its weight gradient's
    operand the same two), and ``W_o``'s input gradient had two results,
    the sigmoid's multiply behind the product."""
    sublayer = trunk.trunk_plan(AFMOE)[0]
    wide = f"[{AFMOE_BOARDS * trunk.SQUARES},{AFMOE.heads * AFMOE.head_dim}]"

    def loss(x, p):
        branch = trunk._attention(x, p, AFMOE, sublayer)[0]
        with jax.named_scope(f"{sublayer.layer}.attention"):
            return jnp.sum(jnp.square(x + trunk._rms_norm(branch, p[sublayer.post_norm], AFMOE.rms_eps)))  # a cotangent that waits for the result

    x = jax.ShapeDtypeStruct((AFMOE_BOARDS * trunk.SQUARES, HIDDEN), jnp.float32, sharding=one_chip)
    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(x, _sublayer_shapes(AFMOE, sublayer, one_chip)).compile().as_text()
    held, fed = _opcodes_fused_with_a_product(text), _opcodes_fused_with_a_product(text, feeding=True)
    assert len(held) == 15, sorted(held)
    assert not {name: sorted(opcodes & {"exponential", "divide"}) for name, opcodes in fed.items() if opcodes & {"exponential", "divide"}}
    products = _products_results_and_operands(text)
    reads_wide = {name: [source for t, source in operands if t == f"f32{wide}"] for name, (_results, operands) in products.items()}
    assert sorted(sources for sources in reads_wide.values() if sources) == [["custom-call"], ["custom-call"]], reads_wide
    assert [results for name, (results, _operands) in products.items() if results[0] == f"bf16{wide}" and "exponential" not in held[name]] == [[f"bf16{wide}"]]


#: Temporaries of the program below as the parent of PR 38 compiled it, bytes: neither form may pass them by more than the
#: lists of a share's held places (``ROOM``). PR 38 reads 2,481,530,880 (kept: the parent's and 137,216 bytes) and 2,078,909,952
#: (recomputed: 133 MB under, the combine's gradient keeps the experts' rows, writes their gradient over them and makes no
#: token-order view again). The loss squares the layer's result, so that the cotangent waits for the forward pass as it does
#: under any layer above this one. Until PR 38 the loss was the plain sum, whose cotangent is a constant: XLA then ran the
#: combine's gradient BEFORE the forward's experts (the parent's schedule: its ``moe_rows_out`` at 367, the forward's first
#: ``gmm`` at 378, its last at 433) and the bytes read were those of an interleaving no step can have, 2,219,951,616 for the
#: kept form against the 2,481,393,664 of the same tree here. PR 38's gradient reads the experts' rows, so that interleaving
#: went and the old program read 3,525,360,128, the forward's view held across the gradient's move: a reading of the test
#: program, not of the layer (PERF.md section 6, PR 38).
PARENT_TEMPORARIES = {False: 2_481_393_664, True: 2_211_950_080}
ROOM = 1 << 20


@pytest.mark.parametrize("recompute", [False, True], ids=["kept", "recomputed"])
def test_a_share_of_the_experts_compiles_at_published_widths(one_chip, compiled_for_tpu, recompute):
    """``value_and_grad`` of ``_experts`` holding 8 of 128 experts over
    131,072 slots: ``gmm`` and ``tgmm`` on the held groups' sizes and weights
    ``[8, 2048, 2 x 1024]`` and ``[8, 1024, 2048]``, the gate kernels between
    them; with ``recompute_experts`` the forward's kernels
    are there a second time, in the backward pass. The moves take the
    held count as their grid's bound (Mosaic compiles a traced grid), the
    sums over a token's slots loop over a tile's count of held slots, the
    buffers keep their shape; and no
    ``cond`` or ``while`` wraps a layer's scope: every ``layerNN.<part>``
    is the second level of its path, where ``benchmark/scopes.py`` reads it."""
    import dataclasses
    import math
    import re

    cfg = dataclasses.replace(AFMOE, recompute_experts=recompute)
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    layer = {"router_w": sds((HIDDEN, 128), jnp.float32), "expert_bias": sds((128,), jnp.float32),
             "experts_gate": sds((8, HIDDEN, WIDTH), jnp.float32), "experts_up": sds((8, HIDDEN, WIDTH), jnp.float32),
             "experts_down": sds((8, WIDTH, HIDDEN), jnp.float32)}

    def loss(n2, p):
        with jax.named_scope("forward"):  # the phase a trainer's step puts first
            return jnp.sum(jnp.square(trunk._experts(n2, p, cfg, "layer01")[0]))  # the cotangent is the result's, not a constant

    compiled = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(sds((AFMOE_BOARDS * trunk.SQUARES, HIDDEN), jnp.float32), layer).compile()
    text = compiled.as_text()
    kernels = [line for line in text.splitlines() if 'custom_call_target="tpu_custom_call"' in line]
    # Six products, the gate pair and six kernels of ``ops/row_move.py``: rows out; rows back and their sum at the tokens; rows
    # out under the scale with the weights' products; rows back and their sum. Made again: two products, the gate and the
    # dispatch's rows out (the combine's gradient needs nothing of its forward but the experts' rows).
    assert len(kernels) == (6 + 2 + 6) + (2 + 1 + 1 if recompute else 0), len(kernels)
    own = [re.match(r"\s*(?:ROOT )?%([\w.\-]+) = ", line).group(1) for line in kernels]
    assert [sum(name in kernel for kernel in own) for name in ("moe_rows_out", "moe_rows_back", "moe_rows_sum")] == [3 if recompute else 2, 2, 2], own
    # On a share nothing under the router, the dispatch or the combine costs by the slot count (PR 38): no XLA gather or scatter
    # of a slot's worth of elements (the chosen scores are a select, the weights' gradient comes through a sort), and nothing
    # but a kernel touches the token-order view.
    routing = [line for line in text.splitlines() if re.search(r"layer01\.(?:router|dispatch|combine)", line)]
    elements = lambda line: math.prod(int(n) for n in re.search(r"= \(?\w+\[([\d,]*)\]", line).group(1).split(",") if n)
    assert len(routing) > 100 and not [line for line in routing if re.search(r" (?:gather|scatter)\(", line) and elements(line) >= 131_072][:2]
    assert not [line for line in text.splitlines() if re.search(r"\[16384,8,16,128\]|\[131072,16,128\]", line) and line not in kernels][:2]
    temporaries = compiled.memory_analysis().temp_size_in_bytes
    assert temporaries <= PARENT_TEMPORARIES[recompute] + ROOM, temporaries
    names = {name for joined in re.findall(r'op_name="([^"]*)"', text) for name in joined.split(";") if re.search(r"layer\d+\.", name)}
    second_level = re.compile(r"jit\(loss\)/(?:jvp\(forward\)|transpose\(jvp\(forward\)\))/layer01\.(?:router|dispatch|experts|combine)(?:/|$)")
    assert len(names) > 100 and not [name for name in names if not second_level.match(name)]
    assert {f"{phase}/layer01.{part}" for phase in ("jvp(forward)", "transpose(jvp(forward))") for part in ("router", "dispatch", "experts", "combine")} == {
        re.match(r"jit\(loss\)/([^/]*/[^/]*)", name).group(1) for name in names}


# -- the third block at the shapes of mla_trunk_train_b256: 256 boards, 32 heads of 128 NoPE + 64 RoPE score columns over --------
# -- 128-wide values through a 512-wide latent, 98,304 slots a routed layer of which 8 of 128 experts are held ----------------

KANANA = trunk.TrunkConfig(heads=32, layers=5, experts=128, experts_per_token=6, expert_width=768, rope_theta=1e6, rms_eps=1e-6,
                           dense_layers=1, dense_width=6144, shared_width=1536, router_score="sigmoid", route_norm=True, route_scale=2.448,
                           held_experts=(0, 8), balance_rate=0.001, recompute_experts=True, kv_lora_rank=512, qk_nope_head_dim=128,
                           qk_rope_head_dim=64, v_head_dim=128)
KANANA_BOARDS = 256
HBM_GIB = 15.75  # what a v5e chip shows


def test_latent_attention_compiles_at_published_widths_and_keeps_no_scores_or_copies_of_the_rope_key(one_chip, compiled_for_tpu):
    """``value_and_grad`` of ``_attention`` with a latent: Mosaic lowers the
    kernel pair at ``[256, 64, 32 x (128 + 64)]`` (a 64-wide RoPE part is
    half a vreg's lanes: two heads a 128-lane tile); the core is still the
    two kernels, each reads the ONE RoPE key ``f32[256,64,64]``, and no
    scores, no per-head view and no key with the RoPE part copied to every
    head exists as an array."""
    import re

    cfg = KANANA
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    sublayer = trunk.trunk_plan(cfg)[0]
    layer = _sublayer_shapes(cfg, sublayer, one_chip)
    assert {k: v.shape for k, v in layer.items()} == {"attn_norm": (2048,), "wq": (2048, 6144), "wkv_a": (2048, 576), "kv_norm": (512,),
                                                      "wkv_b": (512, 8192), "wo": (4096, 2048)}

    def loss(x, p):
        with jax.named_scope("forward"):  # the phase a trainer's step puts first
            return jnp.sum(trunk._latent_attention(x, p, cfg, sublayer)[0])

    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(sds((KANANA_BOARDS * trunk.SQUARES, HIDDEN), jnp.float32), layer).compile().as_text()
    kernels = [line for line in text.splitlines() if 'custom_call_target="tpu_custom_call"' in line]
    assert len(kernels) == 2 and sum("board_attention_grad" in line for line in kernels) == 1
    assert all("operand_layout_constraints={" in line and "f32[256,64,64]{2,1,0}" in line for line in kernels)
    wide = r"\[256,32,64,64\]|\[256,64,32,(?:64|128|192|256)\]|\[256,32,64,(?:128|192|256)\]|\[16384,32,(?:64|128|192|256)\]|\[256,64,6144\]\S* concatenate"
    assert not [line for line in text.splitlines() if re.search(wide, line)][:2]
    for phase in ("jvp(forward)", "transpose(jvp(forward))"):  # the latent's scope beside the attention's, second level of a path
        assert f"jit(loss)/{phase}/layer00.latent/" in text and f"jit(loss)/{phase}/layer00.attention/" in text


#: A cell's own step (its family's trainer from ``benchmark/configs``): the parameters it has to count, the temporaries
#: the parent of PR 36 compiled it to, and the room over them. Both steps are compiled against the chip's memory: XLA
#: remakes arrays in the backward pass (``.remat`` instructions) until the step fits, and stops. Since PR 36 the experts'
#: own program is smaller (the test above) and each step fits with one remade array FEWER than its parent (a 256 MiB
#: ``f32[256,64,4096]`` of attention, a 384 MiB ``f32[16384,6144]`` of the dense layer), so it is faster by that array's
#: remaking and its temporaries read that much higher (PERF.md section 6, PR 36). The room is that array, rounded up: a
#: later PR that needs more than that has remade nothing less and added memory, and shows here.
STEP_CELLS = {"afmoe_trunk": ("trinity-mini-trunk-train", 401_913_678, 10_794_567_680, 96 << 20),
              "mla_trunk": ("kanana-2-trunk-train", 359_558_222, 10_884_026_880, 416 << 20)}


def _copies_of_state_arguments(text: str, floor: int = 1 << 20):
    """The ``copy`` instructions of a compiled step that relayout an argument of the state (``%state_params__...``,
    ``%state_opt_state_...``: same element type in and out) into a result of more than ``floor`` bytes: a leaf the client
    holds in another layout than the step computes it in, copied whole on the way in every step and, donated, on the way
    out (PERF.md section 7: the instruction names are the ledger's ``breakdown`` names). Below the floor are the scalars
    and biases XLA moves to scoped memory; a copy to another type is a cast the step needs, read in the held layout."""
    import math
    import re

    sizes = {"f32": 4, "bf16": 2, "s32": 4}
    types = dict(re.findall(r"%(state_[\w.]+) = (\w+)\[[\d,]*\]\S* parameter\(", text))
    found = []
    for line in text.splitlines():
        match = re.match(r"\s*(?:ROOT )?%[\w.\-]+ = (\w+)\[([\d,]*)\]\S* copy\(%(state_[\w.]+)\)", line)
        if match and match.group(1) == types[match.group(3)] and sizes[match.group(1)] * math.prod(int(n) for n in match.group(2).split(",") if n) > floor:
            found.append(line.strip()[:160])
    return found


def _held_as_the_trainer_holds_it(trainer, one_chip):
    """``trainer`` told that its state lives on the described chip: its own jit, what its step follows asked of that
    chip's client."""
    (device,) = one_chip.device_set
    trainer._hold_on(device)
    return trainer


#: Every cell that ``AzTrainer`` steps, with the leaves the v5e client holds off row-major and the trainer's step follows, and
#: the bytes of each with its two moments: Nemotron's 1,856 is 14.5 lane tiles, so ``experts_up``; every other width is whole lanes.
HELD = {"az": ("az-256x19-train", (), 0), "moe_trunk": ("lladamoe-trunk-train", (), 0), "afmoe_trunk": ("trinity-mini-trunk-train", (), 0),
        "mla_trunk": ("kanana-2-trunk-train", (), 0), "hybrid_trunk": ("nemotron-twotower-trunk-train", ("experts_up",), 3 * 4 * 3 * 8 * 2688 * 1856),
        "cca_trunk": ("zaya1-trunk-train", (), 0), "kda_trunk": ("kimi-linear-trunk-train", (), 0), "gdn_trunk": ("qwen3-next-trunk-train", (), 0),
        "mellum_trunk": ("mellum2-trunk-train", (), 0), "ouro_trunk": ("ouro-2.6b-trunk-train", (), 0)}


@pytest.mark.parametrize("family", HELD)
def test_the_leaves_a_cell_holds_off_row_major(one_chip, family):
    """No compile: the rule (``az_trainer.held_layouts``) asked of the described chip's client, for each cell's own
    trainer. A cell that holds nothing traces the ``_step`` it always did."""
    import importlib
    import json
    from pathlib import Path

    from jax.experimental.layout import Layout

    name, leaves, held_bytes = HELD[family]
    config = json.loads((Path(__file__).resolve().parents[1] / "benchmark" / "configs" / f"{name}.json").read_text())
    trainer = _held_as_the_trainer_holds_it(importlib.import_module(f"benchmark.families.{family}").make_trainer(config), one_chip)
    assert trainer._held == {leaf: Layout(major_to_minor=(0, 1, 3, 2)) for leaf in leaves}
    assert trainer._held_fields == {"layout_held_leaves": 3 * len(leaves), "layout_held_bytes": held_bytes}


@pytest.mark.parametrize("family", STEP_CELLS)
def test_the_whole_step_of_a_share_trunk_fits_one_chip(one_chip, compiled_for_tpu, family):
    """``AzTrainer._step`` on the cut configurations of ``afmoe_trunk_train_b256`` and ``mla_trunk_train_b256`` (one dense and
    four routed layers, 8 of 128 experts held, batch 256, ``recompute_experts``): temporaries and arguments together stay
    under the chip's memory with nothing of attention remade, the temporaries within the room above, every layer's core is
    the attention kernel pair, and no XLA operation under a ``layerNN.experts`` scope has a ``[slots, .]`` result: on a
    share the products, the gate pair and the moves are all that touch the sorted rows."""
    import importlib
    import json
    from pathlib import Path

    name, parameters, parent_temporaries, room = STEP_CELLS[family]
    config = json.loads((Path(__file__).resolve().parents[1] / "benchmark" / "configs" / f"{name}.json").read_text())
    trainer = importlib.import_module(f"benchmark.families.{family}").make_trainer(config)
    boards = config["train"]["batch"]
    sds = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)
    state = jax.tree.map(sds, jax.eval_shape(trainer._init, jax.random.PRNGKey(0)))
    assert sum(x.size for x in jax.tree.leaves(state.params)) == parameters
    batch = {"planes": jax.ShapeDtypeStruct((boards, 8, 8, 19), jnp.float32, sharding=one_chip),
             "policy_target": jax.ShapeDtypeStruct((boards, 4672), jnp.float32, sharding=one_chip),
             "value_target": jax.ShapeDtypeStruct((boards,), jnp.float32, sharding=one_chip)}
    compiled = _held_as_the_trainer_holds_it(trainer, one_chip)._step_jit.lower(state, batch).compile()
    stats = compiled.memory_analysis()
    assert (stats.temp_size_in_bytes + stats.argument_size_in_bytes) / 2**30 < HBM_GIB, stats
    assert stats.temp_size_in_bytes <= parent_temporaries + room, stats.temp_size_in_bytes
    text = compiled.as_text()
    assert not trainer._held and not _copies_of_state_arguments(text)  # whole-lane widths: the client's default is the step's layout
    assert len([line for line in text.splitlines() if "tpu_custom_call" in line and "board_attention" in line]) == 10  # five layers, forward and gradient
    slots = boards * trunk.SQUARES * trainer.cfg.experts_per_token
    assert not _xla_passes_over_slots(text, slots)


# -- the fourth block (nemotron_h) at its published widths: hidden 2688 = 21 x 128, expert width 1856 = 14.5 x 128 ----------------

SSM_BOARDS = 128  # ssm_trunk_train_b128


def test_the_scan_kernel_pair_compiles_at_published_widths(one_chip, compiled_for_tpu):
    """``board_scan`` and ``board_scan_grad`` on a batch of the cell: 64
    heads x 64 in 8 groups, a state of 128, the decay's float32 products
    at ``highest``."""
    from fishnet_tpu.ops.board_scan import board_scan

    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    args = (sds((SSM_BOARDS, 64, 4096), jnp.bfloat16), sds((SSM_BOARDS, 64, 1024), jnp.bfloat16), sds((SSM_BOARDS, 64, 1024), jnp.bfloat16),
            sds((SSM_BOARDS, 64, 64), jnp.float32), sds((64,), jnp.float32), sds((64,), jnp.float32))
    loss = lambda *a: jnp.sum(jnp.square(board_scan(*a, 8, False).astype(jnp.float32)))
    text = jax.jit(jax.value_and_grad(loss, argnums=tuple(range(6)))).lower(*args).compile().as_text()
    kernels = [line for line in text.splitlines() if 'custom_call_target="tpu_custom_call"' in line]
    assert len(kernels) == 2 and sum("board_scan_grad" in line.split(" = ")[0] for line in kernels) == 1, [line.split(" = ")[0] for line in kernels]


def test_the_mixers_two_kernel_pairs_compile_at_published_widths(one_chip, compiled_for_tpu):
    """``mamba_conv`` / ``mamba_conv_grad`` on 128 boards of 4,096 + 1,024 +
    1,024 columns under four taps, and ``mamba_gate_norm`` /
    ``mamba_gate_norm_grad`` on their 8,192 tokens of 4,096 columns in 8
    groups: the blocks fit VMEM twice buffered beside the resident
    gradients of the taps, the bias and the gain."""
    from fishnet_tpu.ops.mamba_mix import mamba_conv, mamba_gate_norm

    sds = lambda shape, dtype=jnp.float32: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    conv_loss = lambda *a: sum(jnp.sum(jnp.square(out.astype(jnp.float32))) for out in mamba_conv(*a, (4096, 1024, 1024), False))
    norm_loss = lambda *a: jnp.sum(jnp.square(mamba_gate_norm(*a, 8, 1e-5, False).astype(jnp.float32)))
    for loss, args, name in ((conv_loss, (sds((SSM_BOARDS, 64, 6144)), sds((6144, 4)), sds((6144,))), "mamba_conv"),
                             (norm_loss, (sds((SSM_BOARDS * 64, 4096), jnp.bfloat16), sds((SSM_BOARDS * 64, 4096)), sds((4096,))), "mamba_gate_norm")):
        text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2))).lower(*args).compile().as_text()
        kernels = [line.split(" = ")[0] for line in text.splitlines() if 'custom_call_target="tpu_custom_call"' in line]
        assert len(kernels) == 2 and sum(f"{name}_grad" in kernel for kernel in kernels) == 1 and all(name in kernel for kernel in kernels), kernels


def _xla_passes_over_a_mixers_chains(text: str):
    """The instructions of a compiled module's entry computation under a ``layerNN.mamba`` scope whose result is ``[128, 64,
    6144]`` (or its ``[8192, 6144]`` view) or ``[8192, 8, 512]`` (or a relayout of it) and that XLA itself computes: neither
    a kernel, nor a view of a kernel's or a product's result, nor a product (a fusion that holds a ``convolution``)."""
    import re

    products = _opcodes_fused_with_a_product(text)
    shaped = re.compile(r"= \S*\[(?:128,64,6144|8192,6144|8192,8,512|1024,8,8,512)\]")
    entry = text[re.search(r"^ENTRY ", text, re.M).start():]
    return [line for line in entry.splitlines() if re.search(r"layer\d+\.mamba", line) and shaped.search(line)
            and not re.search(r" (custom-call|get-tuple-element|bitcast)\(", line) and re.match(r"\s*(?:ROOT )?%([\w.\-]+) = ", line).group(1) not in products]


def test_a_mixer_at_published_widths_is_its_products_the_scan_and_two_kernel_pairs(one_chip, compiled_for_tpu):
    """``value_and_grad`` of ``_mamba`` as a step runs it (one norm, the
    residual) on 128 boards at hidden 2,688: exactly six kernels, and
    between the products and the scan no XLA pass whose result is as large
    as ``[128, 64, 6144]`` or ``[8192, 8, 512]``: until PR 44 the
    convolution, its silu, the cut into x, B, C, the gate and the grouped
    norm were a dozen such passes a mixer, two relayout copies among them."""
    import re

    cfg = trunk.TrunkConfig(hidden=2688, heads=32, kv_heads=2, head_dim=128, qk_norm=False, pattern="MEMEM*E", experts=128, experts_per_token=6,
                            expert_width=1856, gated_ffn=False, shared_width=3712, rope_theta=1e4, rms_eps=1e-5, mamba_heads=64, mamba_head_dim=64,
                            mamba_groups=8, state_size=128, router_score="sigmoid", route_norm=True, route_scale=2.5, held_experts=(0, 8),
                            balance_rate=0.001, recompute_experts=True)
    sds = lambda shape: jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
    mixer = trunk.trunk_plan(cfg)[0]
    layer = _sublayer_shapes(cfg, mixer, one_chip)
    assert mixer.kind == "mamba" and set(layer) == {"layer_norm", *trunk._OWNS["mamba"]}

    def loss(x, p):
        return jnp.sum(jnp.square(x + trunk._mamba(x, p, cfg, mixer)[0]))  # a cotangent that waits for the result

    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(sds((SSM_BOARDS * 64, 2688)), layer).compile().as_text()
    kernels = sorted(line.split(" = ")[0].strip().lstrip("%").split(".")[0] for line in text.splitlines() if 'custom_call_target="tpu_custom_call"' in line)
    assert kernels == ["board_scan", "board_scan_grad", "mamba_conv", "mamba_conv_grad", "mamba_gate_norm", "mamba_gate_norm_grad"], kernels
    assert f"f32[{SSM_BOARDS * 64},6144]" in text or f"f32[{SSM_BOARDS},64,6144]" in text  # the x B C product's result is there to be looked for
    assert not _xla_passes_over_a_mixers_chains(text), _xla_passes_over_a_mixers_chains(text)[:3]
    # the cotangents of the x B C and z products' results leave their kernels bfloat16 and reach the transposed products as they are
    grads = {name: line for line in text.splitlines() for name in ("mamba_conv_grad", "mamba_gate_norm_grad") if re.match(rf"\s*%{name}[.\d]* = ", line)}
    assert "= (bf16[8192,6144]" in grads["mamba_conv_grad"] and "= (bf16[8192,4096]{1,0:T(8,128)(2,1)}, bf16[8192,4096]" in grads["mamba_gate_norm_grad"], grads


def test_the_ungated_experts_compile_at_widths_no_lane_tile_divides(one_chip, compiled_for_tpu):
    """A share's ungated experts at hidden 2,688 and width 1,856, as
    ``_routed`` hands them to Mosaic: rows of 3,072 (whole tiles of a
    moved row), weights padded to them and to 1,920 lanes: two grouped
    products and the squared ReLU forward, four products and its
    gradient backward, under a traced extent; the padding is XLA's and
    no pass of it is over ``[slots, .]``."""
    slots, held, hidden, width = SSM_BOARDS * 64 * 6, 8, 2688, 1856
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    args = (sds((slots, trunk._whole_rows(hidden)), jnp.bfloat16), sds((held, hidden, width), jnp.float32), sds((held, width, hidden), jnp.float32),
            sds((held,), jnp.int32), sds((), jnp.int32))

    def loss(rows, up_w, down_w, group_sizes, extent):
        with jax.named_scope("layer01.experts"):
            out = trunk._expert_ffn(rows, None, up_w, down_w, group_sizes, extent)
        return jnp.sum(jnp.where(jnp.arange(slots)[:, None] < extent, out.astype(jnp.float32), 0.0))

    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2))).lower(*args).compile().as_text()
    kernels = [line for line in text.splitlines() if 'custom_call_target="tpu_custom_call"' in line]
    assert len(kernels) == 8, len(kernels)  # forward 2 products + relu^2; backward 2 gmm + 2 tgmm + its gradient
    assert [line for line in kernels if f"bf16[{slots},1920]" in line and f"bf16[{held},3072,1920]" in line]  # the padded up product
    assert not _xla_passes_over_slots(text, slots)


def test_the_fourth_blocks_step_compiles_at_published_widths(one_chip, compiled_for_tpu):
    """The whole step of ``ssm_trunk_train_b128`` as the trainer compiles it: the scan pair and the
    convolution and gate-norm pairs a mixer, the attention pair at 16 query heads a key-value head without its
    norm, the moves at a row of 3,072, the products at 1,920 lanes; and since PR 45 the update of ``experts_up``
    runs in the layout the client holds it and its two moments in (``{2,3,1,0}``: 2,688 on the lanes), so that no
    state argument is relaid (until then six transposing copies of ``f32[3,8,2688,1856]``, 479 MB each, every step:
    three in, three out of the donated state) and the forward's bfloat16 cast reads the argument itself."""
    import re

    import optax

    from fishnet_tpu.train.az_trainer import AzTrainer

    cfg = trunk.TrunkConfig(hidden=2688, heads=32, kv_heads=2, head_dim=128, qk_norm=False, pattern="MEMEM*E", experts=128, experts_per_token=6,
                            expert_width=1856, gated_ffn=False, shared_width=3712, rope_theta=1e4, rms_eps=1e-5, mamba_heads=64, mamba_head_dim=64,
                            mamba_groups=8, state_size=128, router_score="sigmoid", route_norm=True, route_scale=2.5, held_experts=(0, 8),
                            balance_rate=0.001, recompute_experts=True)
    trainer = AzTrainer(cfg, optimizer=optax.adamw(optax.linear_schedule(0.0, 3e-4, 100_000), weight_decay=1e-4))
    on_chip = lambda tree: jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), tree)
    state = jax.eval_shape(trainer._init, jax.random.PRNGKey(0))
    assert sum(v.size for v in state.params.values()) == 440_339_214  # the configuration file's reckoning
    batch = {"planes": jnp.zeros((SSM_BOARDS, 8, 8, 19)), "policy_target": jnp.zeros((SSM_BOARDS, 4672)), "value_target": jnp.zeros((SSM_BOARDS,))}
    compiled = _held_as_the_trainer_holds_it(trainer, one_chip)._step_jit.lower(on_chip(state), on_chip(batch)).compile()
    text = compiled.as_text()
    assert list(trainer._held) == ["experts_up"] and not _copies_of_state_arguments(text), _copies_of_state_arguments(text)
    assert not re.search(r"= f32\[3,8,2688,1856\]\S* copy\(", text)
    header = text.splitlines()[0]
    layouts = re.findall(r"f32\[3,8,2688,1856\]\{([\d,]+)", header[header.index("entry_computation_layout="):])
    assert layouts == 6 * ["2,3,1,0"], layouts  # the weight and its two moments, arguments and results: the client's one layout
    adamw = [line for line in text.splitlines() if re.match(r"\s*%[\w.]+ = \(f32\[3,8,2688,1856\]\{2,3,1,0\S*, f32\[3,8,2688,1856\]\{2,3,1,0\S*, f32\[3,8,2688,1856\]\{2,3,1,0", line)]
    assert len(adamw) == 1 and " fusion(" in adamw[0], adamw  # the update itself runs in that layout
    assert header.count("-alias)") == len(jax.tree.leaves(state)), header.count("-alias)")  # donation still covers every state leaf
    names = [line.split(" = ")[0] for line in text.splitlines() if 'custom_call_target="tpu_custom_call"' in line]
    assert sum("board_scan_grad" in n for n in names) == 3 and sum("board_scan" in n for n in names) == 6, names
    for pair in ("mamba_conv", "mamba_gate_norm"):  # since PR 44: a mixer's two float32 chains, forward and gradient
        assert sum(f"{pair}_grad" in n for n in names) == 3 and sum(pair in n for n in names) == 6, (pair, names)
    assert sum("board_attention_grad" in n for n in names) == 1 and sum("board_attention" in n for n in names) == 2
    memory = compiled.memory_analysis()
    assert (memory.argument_size_in_bytes + memory.temp_size_in_bytes) / 2 ** 30 < 13.5  # 4.92 + 7.24 GiB when this was written


# -- the fifth block (zaya) at its published widths: 8 query heads over 2 key-value heads of 128 in a 1024 / 256 latent, -----------
# -- 1,280 mixed columns, a 256-wide router MLP choosing one of 16 experts of width 2048, 8 of them held -------------------------

CCA_BOARDS = 512  # cca_trunk_train_b512
CCA = trunk.TrunkConfig(hidden=2048, heads=8, kv_heads=2, head_dim=128, layers=4, cca=(2, 2), rotary_dim=64, router_hidden=256, experts=16,
                        experts_per_token=1, expert_width=2048, rope_theta=5e6, rms_eps=1e-5, held_experts=(0, 8), balance_rate=0.0001,
                        recompute_experts=True)


def test_the_mix_kernel_pair_compiles_at_published_widths(one_chip, compiled_for_tpu):
    """``cca_mix`` and ``cca_mix_grad`` on a batch of the cell: 1,280
    columns, two taps each, conv1's ten heads of ``[128, 128]`` matrices
    and their float32 sums resident across the grid's steps."""
    from fishnet_tpu.ops.cca_mix import cca_mix

    sds = lambda shape, dtype=jnp.float32: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    args = (sds((CCA_BOARDS, 64, 1280)), sds((1280, 2)), sds((1280,)), sds((10, 2, 128, 128)), sds((1280,)))

    def loss(*a):
        q, k, sums = cca_mix(*a, 8, 2, False)
        return jnp.sum(jnp.square(q)) + jnp.sum(jnp.square(k)) + jnp.sum(sums)

    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4))).lower(*args).compile().as_text()
    kernels = [line.split(" = ")[0] for line in text.splitlines() if 'custom_call_target="tpu_custom_call"' in line]
    assert len(kernels) == 2 and sum("cca_mix_grad" in name for name in kernels) == 1, kernels


def test_the_core_with_a_gain_a_key_value_head_and_half_a_head_rotated_compiles_at_published_widths(one_chip, compiled_for_tpu):
    """``value_and_grad`` of the fifth block's ``_attention``: the two
    joined projections, the mix pair, and ``board_attention`` /
    ``board_attention_grad`` told a norm without a query gain, a gain a
    key-value head and RoPE on 64 of 128 columns, 4 query heads a
    key-value head: four kernels, no scores kept, no per-head copy."""
    import re

    sds = lambda shape, dtype=jnp.float32: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    sublayer = trunk.trunk_plan(CCA)[0]
    layer = _sublayer_shapes(CCA, sublayer, one_chip)
    assert layer["wo"].shape == (1024, 2048) and layer["temp"].shape == (2,) and layer["conv1_w"].shape == (10, 2, 128, 128)

    def loss(x, p):
        return jnp.sum(jnp.square(trunk._cca_attention(x, p, CCA, sublayer)[0]))

    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(sds((CCA_BOARDS * trunk.SQUARES, HIDDEN)), layer).compile().as_text()
    kernels = [line.split(" = ")[0] for line in text.splitlines() if 'custom_call_target="tpu_custom_call"' in line]
    assert len(kernels) == 4 and sum("board_attention" in name for name in kernels) == 2 and sum("cca_mix" in name for name in kernels) == 2, kernels
    per_head = [line for line in text.splitlines() if re.search(r"\[512,8,64,64\]|\[512,64,8,128\]|\[512,8,64,128\]", line)]
    assert not per_head, per_head[:2]


def test_the_fifth_blocks_step_compiles_at_published_widths_and_fits_one_chip(one_chip, compiled_for_tpu):
    """The whole step of ``cca_trunk_train_b512`` from its configuration
    file: the mix pair and the attention pair a layer, the experts'
    joined product at 4,096 columns on ~2,048 rows a held expert, the
    router MLP, top-1 without ``moe_rows_sum`` (a token's one slot is a
    select), nothing remade to fit."""
    import importlib
    import json
    from pathlib import Path

    config = json.loads((Path(__file__).resolve().parents[1] / "benchmark" / "configs" / "zaya1-trunk-train.json").read_text())
    trainer = importlib.import_module("benchmark.families.cca_trunk").make_trainer(config)
    assert trainer.cfg == CCA and config["train"]["batch"] == CCA_BOARDS
    on_chip = lambda tree: jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), tree)
    state = jax.eval_shape(trainer._init, jax.random.PRNGKey(0))
    assert sum(v.size for v in state.params.values()) == 427_880_022  # the configuration file's reckoning
    batch = {"planes": jnp.zeros((CCA_BOARDS, 8, 8, 19)), "policy_target": jnp.zeros((CCA_BOARDS, 4672)), "value_target": jnp.zeros((CCA_BOARDS,))}
    compiled = _held_as_the_trainer_holds_it(trainer, one_chip)._step_jit.lower(on_chip(state), on_chip(batch)).compile()
    text = compiled.as_text()
    assert not trainer._held and not _copies_of_state_arguments(text)  # whole-lane widths: the client's default is the step's layout
    names = [line.split(" = ")[0] for line in text.splitlines() if 'custom_call_target="tpu_custom_call"' in line]
    assert sum("cca_mix_grad" in n for n in names) == 4 and sum("cca_mix" in n for n in names) == 8, names
    assert sum("board_attention_grad" in n for n in names) == 4 and sum("board_attention" in n for n in names) == 8
    assert not [n for n in names if "moe_rows_sum" in n] and sum("moe_rows_back" in n for n in names) >= 4
    assert not _xla_passes_over_slots(text, CCA_BOARDS * trunk.SQUARES)  # at one slot a token the slots are the tokens: still the kernels alone
    memory = compiled.memory_analysis()
    assert (memory.argument_size_in_bytes + memory.temp_size_in_bytes) / 2 ** 30 < 13.0  # 4.79 + 7.46 GiB when this was written
    assert ".remat" not in text


# -- the delta mixers' gated head norm (PR 55): ONE kernel pair for the sixth and the seventh block, 128 boards of a head of 128 ----------

HEAD_NORMS = {"gdn": (32, "silu"), "kda": (16, "sigmoid")}  # gdn_trunk_train_b128: 32 value heads under silu(z); kda_trunk_train_b128: 16 held heads under sigmoid(gate)


@pytest.mark.parametrize("heads,gate", HEAD_NORMS.values(), ids=HEAD_NORMS)
def test_the_gated_head_norm_pair_compiles_at_published_widths(one_chip, compiled_for_tpu, heads, gate):
    """``head_norm_gate`` / ``head_norm_gate_grad`` on a batch's 8,192 tokens: ``[8192, 4096]`` in 32 heads under ``silu``,
    ``[8192, 2048]`` in 16 under ``sigmoid``; 128 rows a grid step twice buffered beside the gain's resident gradient, 8 heads of 32 rows a turn
    at dynamic, tile-aligned lane offsets."""
    from fishnet_tpu.ops.mamba_mix import head_norm_gate

    sds = lambda shape, dtype=jnp.float32: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    loss = lambda *a: jnp.sum(jnp.square(head_norm_gate(*a, gate, 1e-6, False).astype(jnp.float32)))
    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2))).lower(sds((8192, heads * 128), jnp.bfloat16), sds((8192, heads * 128)), sds((128,))).compile().as_text()
    kernels = [line.split(" = ")[0] for line in text.splitlines() if 'custom_call_target="tpu_custom_call"' in line]
    assert len(kernels) == 2 and sum("head_norm_gate_grad" in kernel for kernel in kernels) == 1 and all("head_norm_gate" in kernel for kernel in kernels), kernels


def _xla_passes_over_a_head_norm(text: str, heads: int):
    """The instructions of a compiled module's entry computation, under a ``layerNN.gdn`` / ``layerNN.kda`` scope or under none,
    whose result is the ``[8192, heads, 128]`` view of a delta mixer's o, its relayout ``[1024, 8, heads, 128]`` or a float32
    ``[128, 64, heads x 128]``, and that XLA itself computes: neither a kernel, nor a view or an asynchronous move of another's
    result. Until PR 55 a layer held a dozen: a convert, three relayout copies, two broadcasts written out, two reshape copies."""
    import re

    shaped = re.compile(rf"= \(?(?:\S*\[(?:8192,{heads},128|1024,8,{heads},128)\]|f32\[128,64,{heads * 128}\])")
    entry = text[re.search(r"^ENTRY ", text, re.M).start():]
    return [line for line in entry.splitlines() if shaped.search(line) and (re.search(r"layer\d+\.(?:gdn|kda)", line) or "op_name=" not in line)
            and not re.search(r" (custom-call|get-tuple-element|bitcast|copy-start|copy-done|slice-start|slice-done)\(", line)]


def _delta_trainer(block: str):
    """The trainer of a delta cell from its configuration file, as its family makes it."""
    import importlib
    import json
    from pathlib import Path

    name, family = {"gdn": ("qwen3-next-trunk-train", "gdn_trunk"), "kda": ("kimi-linear-trunk-train", "kda_trunk")}[block]
    config = json.loads((Path(__file__).resolve().parents[1] / "benchmark" / "configs" / f"{name}.json").read_text())
    assert config["train"]["batch"] == 128
    return importlib.import_module(f"benchmark.families.{family}").make_trainer(config)


@pytest.mark.parametrize("block", HEAD_NORMS)
def test_a_delta_mixer_at_published_widths_is_its_products_the_core_and_three_kernel_pairs(one_chip, compiled_for_tpu, block):
    """``value_and_grad`` of ``_gdn`` and of ``_kda`` as a step runs them (one norm, the residual) on 128 boards, from the
    cells' configuration files: exactly six kernels, and no XLA pass over the ``[tokens, heads, 128]`` view of o or a float32
    copy of it. The gradient kernel's ``d_o`` and ``d_z`` leave it bfloat16: ``board_delta_grad`` reads the first through a
    view, the transposed products of z's product read the second as it is, and nothing widens either on the way."""
    import re

    cfg = _delta_trainer(block).cfg
    mixer, heads = next(s for s in trunk.trunk_plan(cfg) if s.kind == block), HEAD_NORMS[block][0]
    layer = _sublayer_shapes(cfg, mixer, one_chip)
    assert set(layer) == {"attn_norm", *trunk._OWNS[block]} and layer[f"{block}_o_norm"].shape == (128,)

    def loss(x, p):
        return jnp.sum(jnp.square(x + trunk._KINDS[block][0](x, p, cfg, mixer)[0]))  # a cotangent that waits for the result

    x = jax.ShapeDtypeStruct((128 * trunk.SQUARES, cfg.hidden), jnp.float32, sharding=one_chip)
    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(x, layer).compile().as_text()
    named = ((re.match(r"\s*(?:ROOT )?%([\w.\-]+) = ", line), line) for line in text.splitlines())
    lines = {name.group(1): line for name, line in named if name}
    kernels = sorted(name.split(".")[0] for name, line in lines.items() if 'custom_call_target="tpu_custom_call"' in line)
    assert kernels == ["board_delta", "board_delta_grad", "head_norm_gate", "head_norm_gate_grad", "mamba_conv", "mamba_conv_grad"], kernels
    assert f"bf16[128,64,{heads * 128}]" in text and f"f32[8192,{heads * 128}]" in text  # o and z are there to be looked for
    assert not _xla_passes_over_a_head_norm(text, heads), _xla_passes_over_a_head_norm(text, heads)[:3]
    (gradient,) = [name for name in lines if name.startswith("head_norm_gate_grad")]
    wide = f"bf16[8192,{heads * 128}]"
    assert re.search(rf"= \({re.escape(wide)}\S*, {re.escape(wide)}\S*, f32\[8,{heads * 128}\]", lines[gradient]), lines[gradient][:300]
    results = {int(re.search(r"index=(\d)", line).group(1)): name for name, line in lines.items() if f" get-tuple-element(%{gradient})" in line}
    reads = lambda name: [reader for reader, line in lines.items() if re.search(rf"[(, ]%{re.escape(name)}[,)]", line.split(" = ", 1)[1])]
    views = [reader for reader in reads(results[0]) if " bitcast(" in lines[reader]]
    assert any("board_delta_grad" in reader for view in views for reader in reads(view)), (views, reads(results[0]))  # d_o: a view, then the core's gradient
    products = _opcodes_fused_with_a_product(text)
    readers = [reader for reader in reads(results[1]) if "-start(" not in lines[reader]]  # but XLA's own prefetch of it
    assert readers and all(reader in products for reader in readers), [lines[reader][:200] for reader in readers]  # d_z: the transposed products, nothing between


# -- the sixth block (kimi_linear) at its published widths: hidden 2304 = 18 x 128, 16 held heads of 128 a mixer ------------------------

KDA_BOARDS = 128  # kda_trunk_train_b128
#: What a differentiated ``board_delta`` writes beside o at 128 boards x 16 heads: ``[T | Mk]`` and ``U`` float32, ``Mq`` bfloat16 in half a tile.
KDA_KEPT = [("f32", "128,64,2048"), ("f32", "128,64,2048"), ("bf16", "128,64,2048")]


def _kept_between_the_delta_pairs(text: str):
    """From a compiled module's text, for each ``board_delta_grad``: the forward kernel it reads from, the shapes of what
    that forward writes beside o, and for each of the three what the gradient reads in its place (operands 5-7): the index
    of the forward's own result where it is read as it was written, else the instruction that came between (a ``copy``, a
    ``transpose``)."""
    import re

    named = ((re.match(r"\s*(?:ROOT )?%([\w.\-]+) = ", line), line) for line in text.splitlines())
    lines = {name.group(1): line for name, line in named if name}
    pairs = []
    for gradient, line in lines.items():
        if 'custom_call_target="tpu_custom_call"' not in line or "board_delta_grad" not in gradient:
            continue
        operands = [re.sub(r"/\*.*?\*/", "", operand).strip().lstrip("%") for operand in re.search(r" custom-call\((.*?)\), custom_call_target", line).group(1).split(",")]
        assert len(operands) == 9, operands  # q, k, v, g, beta, [T | Mk], U, Mq, o's cotangent
        straight = [re.search(r" get-tuple-element\(%([\w.\-]+)\), index=(\d)", lines[operand]) for operand in operands[5:8]]
        forward = {found.group(1) for found in straight if found}
        assert len(forward) == 1 and "board_delta" in min(forward) and "grad" not in min(forward), [lines[operand][:160] for operand in operands[5:8]]
        written = re.findall(r"(bf16|f32)\[([\d,]+)\]", lines[min(forward)].split(" custom-call(")[0])[1:]
        pairs.append((min(forward), written, [int(found.group(2)) if found else lines[operand].strip()[:160] for found, operand in zip(straight, operands[5:8])]))
    return pairs


def test_the_delta_kernel_pair_compiles_at_published_widths(one_chip, compiled_for_tpu):
    """``board_delta`` and ``board_delta_grad`` on a batch of the cell: 16
    held heads x 128, a head of eight boards a grid step, the six levels'
    masks from bit operations on iotas, the cumulative sum and the solve's
    float32 products at ``highest``, a board's ``[64, 16]`` block of dbeta
    resident over the heads' steps. Differentiated, the forward writes
    beside o the three kept arrays (``[T | Mk]`` and ``U`` float32, ``Mq``
    bfloat16 in half of a tile: 80 KB a board and head of the 96 budgeted),
    the gradient reads them as they were written, nothing of XLA's between;
    the primal writes o alone."""
    from fishnet_tpu.ops.board_delta import board_delta

    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    wide = (KDA_BOARDS, 64, 16 * 128)
    args = (sds(wide, jnp.bfloat16), sds(wide, jnp.bfloat16), sds(wide, jnp.bfloat16), sds(wide, jnp.float32), sds((KDA_BOARDS, 64, 16), jnp.float32))
    loss = lambda *a: jnp.sum(jnp.square(board_delta(*a, False).astype(jnp.float32)))
    text = jax.jit(jax.value_and_grad(loss, argnums=tuple(range(5)))).lower(*args).compile().as_text()
    kernels = [line.split(" = ")[0] for line in text.splitlines() if 'custom_call_target="tpu_custom_call"' in line]
    assert len(kernels) == 2 and sum("board_delta_grad" in kernel for kernel in kernels) == 1 and all("board_delta" in kernel for kernel in kernels), kernels
    ((_, written, read),) = _kept_between_the_delta_pairs(text)
    assert written == KDA_KEPT and read == [1, 2, 3], (written, read)
    sizes = {"f32": 4, "bf16": 2}
    assert sum(sizes[dtype] * math.prod(int(n) for n in shape.split(",")) for dtype, shape in written) == KDA_BOARDS * 16 * 80 * 1024  # of the 96 KB budgeted a board and head
    primal = jax.jit(lambda *a: board_delta(*a, False)).lower(*args).compile().as_text()
    (alone,) = [line for line in primal.splitlines() if 'custom_call_target="tpu_custom_call"' in line]
    assert " = bf16[128,64,2048]" in alone and "board_delta" in alone.split(" = ")[0], alone[:200]  # one kernel, one result: no tuple


def _bodies_entered(monkeypatch, module, bodies, calls):
    """A counter laid over each of the kernel ``bodies`` of ``module`` -> how often Python entered each, so far. The jitted ``calls``
    that hold them forget the traces they hold (of another test of this process), so the next program traces its own."""
    entered = dict.fromkeys(bodies, 0)

    def counting(name, body):
        def counted(*refs, **static):
            entered[name] += 1
            return body(*refs, **static)
        return counted

    for name in entered:
        monkeypatch.setattr(module, name, counting(name, getattr(module, name)))
    for call in calls:
        getattr(module, call).clear_cache()
    return entered


def _delta_bodies_entered(monkeypatch, bodies=("_forward_kernel", "_backward_kernel")):
    from fishnet_tpu.ops import board_delta

    return _bodies_entered(monkeypatch, board_delta, bodies, ("_forward_call", "_gradient_call"))


def _head_norm_bodies_entered(monkeypatch):
    from fishnet_tpu.ops import mamba_mix

    return _bodies_entered(monkeypatch, mamba_mix, ("_head_norm_kernel", "_head_norm_grad_kernel"), ("_head_norm_call", "_head_norm_grad_call"))


def test_the_sixth_blocks_step_compiles_at_published_widths_and_fits_one_chip(one_chip, compiled_for_tpu, monkeypatch):
    """The whole step of ``kda_trunk_train_b128`` from its configuration
    file: the delta pair and the convolution pair a KDA layer (four), the
    latent form of the attention pair on the one latent layer (under the
    tables that turn nothing), a moved row of 2,304 = 18 lane tiles as
    3,072, no leaf held off row-major, nothing remade to fit. Lowering it
    enters each kernel body of the delta pair ONCE: the four layers share
    the trace of a jitted call, which ``setup_s`` pays at every start."""
    import importlib
    import json
    from pathlib import Path

    config = json.loads((Path(__file__).resolve().parents[1] / "benchmark" / "configs" / "kimi-linear-trunk-train.json").read_text())
    trainer = importlib.import_module("benchmark.families.kda_trunk").make_trainer(config)
    cfg = trainer.cfg
    assert (cfg.mixers, cfg.nope_layers, cfg.kda_heads, cfg.heads, cfg.hidden) == (("kda", "kda", "kda", "latent", "kda"), (3,), 16, 16, 2304)
    assert config["train"]["batch"] == KDA_BOARDS
    on_chip = lambda tree: jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), tree)
    state = jax.eval_shape(trainer._init, jax.random.PRNGKey(0))
    assert sum(v.size for v in state.params.values()) == 416_608_910  # the configuration file's reckoning
    batch = {"planes": jnp.zeros((KDA_BOARDS, 8, 8, 19)), "policy_target": jnp.zeros((KDA_BOARDS, 4672)), "value_target": jnp.zeros((KDA_BOARDS,))}
    entered, normed = _delta_bodies_entered(monkeypatch), _head_norm_bodies_entered(monkeypatch)
    lowered = _held_as_the_trainer_holds_it(trainer, one_chip)._step_jit.lower(on_chip(state), on_chip(batch))
    # the start-up tripwire: four KDA layers, ONE trace of each kernel body (4 and 4 for bare calls; ``tests/test_board_delta.py`` holds the boards' loop rolled)
    assert entered == {"_forward_kernel": 1, "_backward_kernel": 1} and normed == {"_head_norm_kernel": 1, "_head_norm_grad_kernel": 1}, (entered, normed)
    compiled = lowered.compile()
    text = compiled.as_text()
    assert not trainer._held and not _copies_of_state_arguments(text)  # whole-lane widths: the client's default is the step's layout
    names = [line.split(" = ")[0] for line in text.splitlines() if 'custom_call_target="tpu_custom_call"' in line]
    assert sum("board_delta_grad" in n for n in names) == 4 and sum("board_delta" in n for n in names) == 8, names
    assert sum("mamba_conv_grad" in n for n in names) == 4 and sum("mamba_conv" in n for n in names) == 8
    assert sum("head_norm_gate_grad" in n for n in names) == 4 and sum("head_norm_gate" in n for n in names) == 8  # since PR 55: once a KDA layer
    assert not _xla_passes_over_a_head_norm(text, cfg.kda_heads), _xla_passes_over_a_head_norm(text, cfg.kda_heads)[:3]
    assert sum("board_attention_grad" in n for n in names) == 1 and sum("board_attention" in n for n in names) == 2
    for phase in ("jvp(forward)", "transpose(jvp(forward))"):  # the core's scope beside the mixer's, the latent's beside the attention's
        assert all(f"{phase}/layer0{i}.delta/" in text and f"{phase}/layer0{i}.kda/" in text for i in (0, 1, 2, 4)) and f"{phase}/layer03.delta/" not in text
        assert f"{phase}/layer03.latent/" in text and f"{phase}/layer03.attention/" in text
    assert not _xla_passes_over_slots(text, KDA_BOARDS * trunk.SQUARES * cfg.experts_per_token)
    pairs = _kept_between_the_delta_pairs(text)  # every layer's gradient reads its own forward's kept arrays as they were written
    assert len({forward for forward, _, _ in pairs}) == 4 and all(written == KDA_KEPT and read == [1, 2, 3] for _, written, read in pairs), pairs
    memory = compiled.memory_analysis()
    # 4.66 + 8.51 GiB when this was written: 4.66 + 7.88 before the four forwards kept their tables (4 x 160 MiB), and the same 0.45 GiB of room
    assert (memory.argument_size_in_bytes + memory.temp_size_in_bytes) / 2 ** 30 < 13.62, (memory.argument_size_in_bytes / 2 ** 30, memory.temp_size_in_bytes / 2 ** 30)
    assert ".remat" not in text


# -- the seventh block (qwen3_next) at its published widths: 16 key and 32 value GDN heads of 128, attention heads of 256 ---------------

GDN_BOARDS = 128  # gdn_trunk_train_b128
#: What the second form of a differentiated ``board_delta`` writes beside o at 128 boards: ``T`` float32, a key head's two value heads side by
#: side in one 128-lane tile, and ``U`` float32 in v's columns: 48 KB a board and value head.
GDN_KEPT = [("f32", "128,64,2048"), ("f32", "128,64,4096")]


def _delta_pairs_operands(text: str):
    """From a compiled module's text, the operand shapes of every ``board_delta`` / ``board_delta_grad`` custom call, by the call's name."""
    import re

    named = ((re.match(r"\s*(?:ROOT )?%([\w.\-]+) = ", line), line) for line in text.splitlines())
    lines = {name.group(1): line for name, line in named if name}
    found = {}
    for name, line in lines.items():
        if 'custom_call_target="tpu_custom_call"' not in line or "board_delta" not in name:
            continue
        operands = [re.sub(r"/\*.*?\*/", "", operand).strip().lstrip("%") for operand in re.search(r" custom-call\((.*?)\), custom_call_target", line).group(1).split(",")]
        found[name] = [re.search(r" = \(?((?:bf16|f32)\[[\d,]+\])", lines[operand]).group(1) if operand in lines else operand for operand in operands]
    return found


def test_the_second_form_of_the_delta_pair_compiles_at_published_widths(one_chip, compiled_for_tpu):
    """``board_delta`` and ``board_delta_grad`` told a decay a head, on a batch of the cell: q and k at 16 key heads, v at 32
    value heads, g and beta ``[128, 64, 32]``: a key head of eight boards and its two value heads a grid step, the span sums
    and the solve's float32 products at ``highest``, a board's ``[64, 32]`` blocks of dg and dbeta resident over the key heads'
    steps. Differentiated, the forward writes beside o ``T`` and ``U`` alone and the gradient reads them as they were written."""
    import re

    from fishnet_tpu.ops.board_delta import board_delta

    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    key, value, heads = (GDN_BOARDS, 64, 16 * 128), (GDN_BOARDS, 64, 32 * 128), (GDN_BOARDS, 64, 32)
    args = (sds(key, jnp.bfloat16), sds(key, jnp.bfloat16), sds(value, jnp.bfloat16), sds(heads, jnp.float32), sds(heads, jnp.float32))
    loss = lambda *a: jnp.sum(jnp.square(board_delta(*a, False).astype(jnp.float32)))
    text = jax.jit(jax.value_and_grad(loss, argnums=tuple(range(5)))).lower(*args).compile().as_text()
    kernels = [line.split(" = ")[0] for line in text.splitlines() if 'custom_call_target="tpu_custom_call"' in line]
    assert len(kernels) == 2 and sum("board_delta_grad" in kernel for kernel in kernels) == 1 and all("board_delta" in kernel for kernel in kernels), kernels
    operands = _delta_pairs_operands(text)
    inputs = ["bf16[128,64,2048]", "bf16[128,64,2048]", "bf16[128,64,4096]", "f32[128,64,32]", "f32[128,64,32]"]
    forward, gradient = (next(v for k, v in operands.items() if ("grad" in k) == wanted) for wanted in (False, True))
    assert forward == inputs, forward  # q and k ONCE a key head, one decay a value head: nothing repeated, nothing broadcast
    assert gradient[:5] == inputs and len(gradient) == 8, gradient  # the five inputs, T, U and o's cotangent
    (written,) = [line for line in text.splitlines() if 'custom_call_target="tpu_custom_call"' in line and "board_delta_grad" not in line.split(" = ")[0]]
    assert re.findall(r"(bf16|f32)\[([\d,]+)\]", written.split(" custom-call(")[0])[1:] == GDN_KEPT, written[:300]
    primal = jax.jit(lambda *a: board_delta(*a, False)).lower(*args).compile().as_text()
    (alone,) = [line for line in primal.splitlines() if 'custom_call_target="tpu_custom_call"' in line]
    assert " = bf16[128,64,4096]" in alone and "board_delta" in alone.split(" = ")[0], alone[:200]  # one kernel, one result: no tuple


def _exact_products_a_turn(jaxpr):
    """From a jaxpr (and the jaxprs its equations hold): the operand shapes of every product at ``highest`` inside the ONE rolled
    loop of the ``board_delta`` forward kernel's body, and the loop's turns."""
    def walk(held):
        for eqn in held.eqns:
            yield eqn
            for inner in jax.core.jaxprs_in_params(eqn.params):
                yield from walk(inner)

    (forward,) = [eqn for eqn in walk(jaxpr) if eqn.primitive.name == "pallas_call" and eqn.params["name"] == "board_delta"]
    (loop,) = [eqn for eqn in forward.params["jaxpr"].eqns if eqn.primitive.name in ("scan", "while")]
    highest = lambda precision: precision is not None and set(precision if isinstance(precision, tuple) else (precision,)) == {jax.lax.Precision.HIGHEST}
    (body,) = [inner for inner in jax.core.jaxprs_in_params(loop.params) if any(eqn.primitive.name == "dot_general" for eqn in walk(inner))]
    products = [tuple(v.aval.shape for v in eqn.invars) for eqn in walk(body) if eqn.primitive.name == "dot_general" and highest(eqn.params["precision"])]
    return sorted(products), loop.params["length"]


#: The solve's eleven products at ``highest`` a loop turn: a level's two, levels 1 to 5 (level 0 is ``I - A_0``, no product), and ``U``.
#: Packed: two chains side by side.
_PACKED_SOLVE = [((64, 128), (128, 128))] * 10 + [((128, 128), (128, 128))]
_SINGLE_SOLVE = [((64, 64), (64, 64))] * 10 + [((64, 64), (64, 128))]
_SPANS = ((64, 64), (64, 128))  # a triangle times g: the first form's cumulative sum a board, the second form's spans of a pair of value heads (of ONE: [64, 64])


@pytest.mark.parametrize("shape,wanted", [
    # gdn_trunk_train_b128: a key head's two value heads a turn are ONE packed chain, their spans ONE product; eight boards a block, a board a turn
    (dict(boards=GDN_BOARDS, key_heads=16, value_heads=32), (sorted(_PACKED_SOLVE + [_SPANS]), 8)),
    # kda_trunk_train_b128: boards 2 t and 2 t + 1 of a block of eight a turn, a cumulative sum each
    (dict(boards=KDA_BOARDS, heads=16), (sorted(_PACKED_SOLVE + [_SPANS] * 2), 4)),
    (dict(boards=9, heads=16), (sorted(_SINGLE_SOLVE + [_SPANS]), 1)),  # an odd block: one board a turn, the single chain
    (dict(boards=GDN_BOARDS, key_heads=16, value_heads=16), (sorted(_SINGLE_SOLVE + [((64, 64), (64, 64))]), 8)),  # one value head a key head
    (dict(boards=GDN_BOARDS, key_heads=16, value_heads=48), (sorted(_PACKED_SOLVE + [_SPANS] + _SINGLE_SOLVE + [((64, 64), (64, 64))]), 8)),  # three: a pair and one left over
], ids=["gdn_cell", "kda_cell", "an_odd_block", "one_value_head_a_key_head", "three_value_heads_a_key_head"])
def test_the_delta_forward_solves_two_chains_a_product_where_the_shapes_allow(shape, wanted):
    """The mechanism's counter (engagement is static: a shape decides it while the body is traced). On the two cells' shapes every
    product of the solve in the forward kernel's body is ``[64, 128] x [128, 128]`` at ``highest`` (two chains a product), ten a
    loop turn (five levels of two: level 0 has none), and none ``[64, 64] x [64, 64]``: the packed path runs for every chain of both cells, the single one for none; an odd block of
    boards or an odd value head shows the single chain. Nothing compiles here: the jaxpr of the traced call is read."""
    from fishnet_tpu.ops.board_delta import board_delta

    boards, sds = shape["boards"], jax.ShapeDtypeStruct
    if "heads" in shape:  # the first form: a decay a channel
        wide = (boards, 64, shape["heads"] * 128)
        args = (sds(wide, jnp.bfloat16),) * 3 + (sds(wide, jnp.float32), sds((boards, 64, shape["heads"]), jnp.float32))
    else:
        key, value, heads = (boards, 64, shape["key_heads"] * 128), (boards, 64, shape["value_heads"] * 128), (boards, 64, shape["value_heads"])
        args = (sds(key, jnp.bfloat16),) * 2 + (sds(value, jnp.bfloat16), sds(heads, jnp.float32), sds(heads, jnp.float32))
    for differentiated in (False, True):
        fn = (lambda *a: jax.vjp(lambda *b: board_delta(*b, False), *a)[0]) if differentiated else (lambda *a: board_delta(*a, False))
        assert _exact_products_a_turn(jax.make_jaxpr(fn)(*args).jaxpr) == wanted


@pytest.mark.parametrize("shape", [dict(boards=9, heads=2), dict(boards=8, key_heads=2, value_heads=6), dict(boards=8, key_heads=2, value_heads=2), dict(boards=9, key_heads=2, value_heads=4)],
                         ids=["an_odd_block", "three_value_heads_a_key_head", "one_value_head_a_key_head", "a_pair_of_value_heads_on_an_odd_block"])
def test_the_delta_pairs_single_chain_fall_backs_compile(one_chip, compiled_for_tpu, shape):
    """What no cell runs but the shapes allow: the pair, forward and gradient, where a block of boards is odd (one board a loop turn,
    the pair's lines on ``[64, .]``) and where a key head's value heads are odd (a pair and one left over in one body, or one alone), at
    a head of 128 columns. Mosaic takes each (the interpreter takes anything): two kernels, no fall-back of XLA's."""
    from fishnet_tpu.ops.board_delta import board_delta

    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    boards = shape["boards"]
    if "heads" in shape:
        wide = (boards, 64, shape["heads"] * 128)
        args = (sds(wide, jnp.bfloat16),) * 3 + (sds(wide, jnp.float32), sds((boards, 64, shape["heads"]), jnp.float32))
    else:
        by_head = sds((boards, 64, shape["value_heads"]), jnp.float32)
        args = (sds((boards, 64, shape["key_heads"] * 128), jnp.bfloat16),) * 2 + (sds((boards, 64, shape["value_heads"] * 128), jnp.bfloat16), by_head, by_head)
    loss = lambda *a: jnp.sum(jnp.square(board_delta(*a, False).astype(jnp.float32)))
    text = jax.jit(jax.value_and_grad(loss, argnums=tuple(range(5)))).lower(*args).compile().as_text()
    kernels = [line.split(" = ")[0] for line in text.splitlines() if 'custom_call_target="tpu_custom_call"' in line]
    assert len(kernels) == 2 and sum("board_delta_grad" in kernel for kernel in kernels) == 1 and all("board_delta" in kernel for kernel in kernels), kernels


def test_gated_attention_at_a_head_of_256_with_64_columns_turned_compiles_at_published_widths(one_chip, compiled_for_tpu):
    """``value_and_grad`` of the seventh block's ``_attention``: the grouped normed form at ``head_dim`` 256, 8 query heads on each
    of 2 key-value heads (2 boards a grid step), RoPE on the first 64 of 256 columns, and ``_gated_out`` at 4,096 columns: two
    kernels, no scores kept, no per-head copy."""
    import re

    cfg = _delta_trainer("gdn").cfg
    sublayer = next(s for s in trunk.trunk_plan(cfg) if s.kind == "attention")
    assert (sublayer.layer, sublayer.rope, cfg.head_dim, cfg.heads, cfg.kv_heads, cfg.rotary_dim) == ("layer03", True, 256, 16, 2, 64)
    layer = _sublayer_shapes(cfg, sublayer, one_chip)
    assert layer["wq"].shape == layer["wgate"].shape == (2048, 4096) and layer["wk"].shape == (2048, 512) and layer["q_norm"].shape == (256,)

    def loss(x, p):
        return jnp.sum(jnp.square(trunk._attention(x, p, cfg, sublayer)[0]))

    x = jax.ShapeDtypeStruct((GDN_BOARDS * trunk.SQUARES, 2048), jnp.float32, sharding=one_chip)
    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(x, layer).compile().as_text()
    kernels = [line.split(" = ")[0] for line in text.splitlines() if 'custom_call_target="tpu_custom_call"' in line]
    assert len(kernels) == 2 and sum("board_attention_grad" in name for name in kernels) == 1 and all("board_attention" in name for name in kernels), kernels
    per_head = [line for line in text.splitlines() if re.search(r"\[128,16,64,64\]|\[128,64,16,256\]|\[128,16,64,256\]", line)]
    assert not per_head, per_head[:2]


def test_the_seventh_blocks_step_compiles_at_published_widths_and_fits_one_chip(one_chip, compiled_for_tpu, monkeypatch):
    """The whole step of ``gdn_trunk_train_b128`` from its configuration file: the delta pair (its second form) and the
    convolution pair a GDN layer (three), the grouped normed attention pair at a head of 256 on the one attention layer, no
    leaf held off row-major, nothing remade to fit. Lowering it enters each of the second form's kernel bodies ONCE and the
    first form's never: the three layers share the trace of a jitted call, which ``setup_s`` pays at every start. The
    operands of every delta call are the layer's own arrays: q and k at 16 key heads, g and beta ``[128, 64, 32]``."""
    trainer = _delta_trainer("gdn")
    cfg = trainer.cfg
    assert (cfg.mixers, cfg.linear_num_key_heads, cfg.linear_num_value_heads, cfg.head_dim, cfg.hidden) == (("gdn", "gdn", "gdn", "attention"), 16, 32, 256, 2048)
    on_chip = lambda tree: jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), tree)
    state = jax.eval_shape(trainer._init, jax.random.PRNGKey(0))
    assert sum(v.size for v in state.params.values()) == 346_814_094  # the configuration file's reckoning
    batch = {"planes": jnp.zeros((GDN_BOARDS, 8, 8, 19)), "policy_target": jnp.zeros((GDN_BOARDS, 4672)), "value_target": jnp.zeros((GDN_BOARDS,))}
    entered = _delta_bodies_entered(monkeypatch, ("_head_forward_kernel", "_head_backward_kernel", "_forward_kernel", "_backward_kernel"))
    normed = _head_norm_bodies_entered(monkeypatch)
    lowered = _held_as_the_trainer_holds_it(trainer, one_chip)._step_jit.lower(on_chip(state), on_chip(batch))
    assert entered == {"_head_forward_kernel": 1, "_head_backward_kernel": 1, "_forward_kernel": 0, "_backward_kernel": 0}, entered
    assert normed == {"_head_norm_kernel": 1, "_head_norm_grad_kernel": 1}, normed  # three GDN layers, ONE trace of each body of the head norm's pair
    compiled = lowered.compile()
    text = compiled.as_text()
    assert not trainer._held and not _copies_of_state_arguments(text)
    names = [line.split(" = ")[0] for line in text.splitlines() if 'custom_call_target="tpu_custom_call"' in line]
    assert sum("board_delta_grad" in n for n in names) == 3 and sum("board_delta" in n for n in names) == 6, names
    assert sum("mamba_conv_grad" in n for n in names) == 3 and sum("mamba_conv" in n for n in names) == 6
    assert sum("head_norm_gate_grad" in n for n in names) == 3 and sum("head_norm_gate" in n for n in names) == 6  # since PR 55: once a GDN layer
    assert not _xla_passes_over_a_head_norm(text, cfg.linear_num_value_heads), _xla_passes_over_a_head_norm(text, cfg.linear_num_value_heads)[:3]
    assert sum("board_attention_grad" in n for n in names) == 1 and sum("board_attention" in n for n in names) == 2
    for phase in ("jvp(forward)", "transpose(jvp(forward))"):  # the core's scope beside the mixer's; the shared expert under its own
        assert all(f"{phase}/layer0{i}.delta/" in text and f"{phase}/layer0{i}.gdn/" in text for i in (0, 1, 2)) and f"{phase}/layer03.delta/" not in text
        assert f"{phase}/layer03.attention/" in text and all(f"{phase}/layer0{i}.shared/" in text for i in range(4))
    assert not _xla_passes_over_slots(text, GDN_BOARDS * trunk.SQUARES * cfg.experts_per_token)
    inputs = ["bf16[128,64,2048]", "bf16[128,64,2048]", "bf16[128,64,4096]", "f32[128,64,32]", "f32[128,64,32]"]
    operands = _delta_pairs_operands(text)
    assert len(operands) == 6 and all(shapes[:5] == inputs for shapes in operands.values()), operands
    memory = compiled.memory_analysis()
    # 3.88 + 9.75 GiB when this was written, of the chip's 15.75: a GDN layer keeps the convolution's float32 operand, z, the three
    # bfloat16 results, ``T`` and ``U`` (0.9 GiB at 8,192 tokens)
    assert (memory.argument_size_in_bytes + memory.temp_size_in_bytes) / 2 ** 30 < 14.0, (memory.argument_size_in_bytes / 2 ** 30, memory.temp_size_in_bytes / 2 ** 30)
    assert ".remat" not in text


# -- the eighth block (mellum) at its published widths: hidden 2304 = 18 x 128 (a moved row padded to 3,072), expert width 896 = 7 x 128 --------

MELLUM_BOARDS = 256  # mellum_trunk_train_b256


def test_the_eighth_blocks_step_compiles_at_published_widths_and_fits_one_chip(one_chip, compiled_for_tpu):
    """The whole step of ``mellum_trunk_train_b256`` from its configuration file: the grouped normed attention pair on all four
    layers (8 query heads a key-value head of 128 at hidden 2,304: 2 boards a grid step), each told its tables as an OPERAND
    (``f32[64,128]`` twice a call: the plain ones on the three sliding layers, YaRN's on the full one, the kernels the same), the
    grouped products at 896 and 1,792 columns over a contraction of 3,072 (a moved row of 2,304 = 18 lane tiles goes as 24), no
    leaf held off row-major, nothing remade to fit, arguments and temporaries under the chip's memory with ``recompute_experts``
    (3.18 + 7.03 GiB when this was written; without it 3.18 + 11.65 and XLA's own rematerialisation)."""
    import importlib
    import json
    import re
    from pathlib import Path

    config = json.loads((Path(__file__).resolve().parents[1] / "benchmark" / "configs" / "mellum2-trunk-train.json").read_text())
    assert config["train"]["batch"] == MELLUM_BOARDS and config["train"]["recompute_experts"] is True
    trainer = importlib.import_module("benchmark.families.mellum_trunk").make_trainer(config)
    cfg = trainer.cfg
    assert (cfg.hidden, cfg.heads, cfg.kv_heads, cfg.head_dim, cfg.expert_width, cfg.held, cfg.full_attention_layers) == (2304, 32, 4, 128, 896, (0, 8), (3,))
    assert trunk._whole_rows(cfg.hidden) == 3072 and trunk._whole_lanes(cfg.expert_width) == 896
    assert (trunk._tiling(131_072, 3072, 1792), trunk._tiling(131_072, 896, 3072)) == ((512, 1024, 896), (512, 896, 1024))
    on_chip = lambda tree: jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), tree)
    state = jax.eval_shape(trainer._init, jax.random.PRNGKey(0))
    assert sum(v.size for v in state.params.values()) == 284_016_718  # the configuration file's reckoning
    batch = {"planes": jnp.zeros((MELLUM_BOARDS, 8, 8, 19)), "policy_target": jnp.zeros((MELLUM_BOARDS, 4672)), "value_target": jnp.zeros((MELLUM_BOARDS,))}
    compiled = _held_as_the_trainer_holds_it(trainer, one_chip)._step_jit.lower(on_chip(state), on_chip(batch)).compile()
    text = compiled.as_text()
    assert not trainer._held and not _copies_of_state_arguments(text)
    calls = [line for line in text.splitlines() if 'custom_call_target="tpu_custom_call"' in line and "board_attention" in line]
    assert len(calls) == 8 and sum("board_attention_grad" in line.split(" = ")[0] for line in calls) == 4  # four layers, forward and gradient
    for line in calls:  # q a group of eight query heads wide, k and v at the four key-value heads, the two tables as operands
        shapes = re.findall(r"(?:f32|bf16)\[[\d,]*\]", line.split("custom-call(")[1])
        assert shapes[:3] == ["f32[256,64,4096]", "f32[256,64,512]", "bf16[256,64,512]"] and shapes[5:7] == ["f32[64,128]", "f32[64,128]"], shapes
    for phase in ("jvp(forward)", "transpose(jvp(forward))"):
        assert all(f"{phase}/layer0{i}.{part}/" in text for i in range(4) for part in ("attention", "router", "dispatch", "experts", "combine"))
        assert ".shared/" not in text and ".dense/" not in text and ".latent/" not in text
    assert not _xla_passes_over_slots(text, MELLUM_BOARDS * trunk.SQUARES * cfg.experts_per_token)
    memory = compiled.memory_analysis()
    assert (memory.argument_size_in_bytes + memory.temp_size_in_bytes) / 2 ** 30 < 11.0, (memory.argument_size_in_bytes / 2 ** 30, memory.temp_size_in_bytes / 2 ** 30)
    assert ".remat" not in text


# -- the ninth block (sdar_moe) at its published widths: block diffusion over a board, 128 tokens a board under the block mask ------------------

SDAR_BOARDS = 128  # sdar_trunk_train_b128


def test_the_masked_kernel_pair_compiles_at_published_widths_for_both_copies_and_for_the_clean_one(one_chip, compiled_for_tpu):
    """``board_attention_blocks`` and its gradient at the cell's shape (128 boards of 128 rows, 8 query heads a key-value head of 128, the heads
    two a product since PR 63: FOUR boards a grid step and a loop body, the fastest of PR 63's sweep on the chip; double-buffered the gradient's
    blocks are 11.5 MiB of VMEM) and at the served one (the clean copy alone, 64 rows, 8 boards a step): Mosaic takes the row slices of a copy,
    a pair's queries stacked along the rows (256 rows normed and turned a pass), the 128 x 128 scores under a bias of -inf and the sums of dk, dv
    over both copies' queries; the mask is an operand laid twice along a pair's queries (``f32[192,128]``, ``f32[64,128]``), the tables are laid
    once a copy (``f32[128,128]``), and no scores are kept between the two."""
    import re

    from fishnet_tpu.ops import board_attention as kernels
    from fishnet_tpu.ops.board_attention import board_attention

    assert (kernels._BLOCKS_PAIRED_BOARDS, kernels._BLOCKS_PAIRED_UNROLL) == (64, 64)  # (board, head, copy)s: 4 boards of 8 heads x 2 copies a step, and a body
    for streams, bias, boards_a_step in ((2, "f32[192,128]", 4), (1, "f32[64,128]", 8)):
        rows = 64 * streams
        shapes = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip) for shape, dtype in (
            ((SDAR_BOARDS, rows, 4096), jnp.float32), ((SDAR_BOARDS, rows, 512), jnp.float32), ((SDAR_BOARDS, rows, 512), jnp.bfloat16), ((128,), jnp.float32), ((128,), jnp.float32))]
        grid, group, *_ = kernels._stream_blocks(shapes[0], shapes[1], 128, streams)
        assert (grid, group) == ((SDAR_BOARDS // boards_a_step, 4), 8) and kernels._blocks_unroll(False, kernels._UNROLL_GRAD, group, streams) == boards_a_step
        loss = lambda q, k, v, g_q, g_k: jnp.sum(jnp.square(board_attention(q, k, v, g_q, g_k, 1e6, 1e-6, False, block_length=4, streams=streams).astype(jnp.float32)))
        text = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(*shapes).compile().as_text()
        calls = [line for line in text.splitlines() if 'custom_call_target="tpu_custom_call"' in line]
        assert len(calls) == 2 and "board_attention_blocks_grad" in calls[1].split(" = ")[0] and "board_attention_blocks" in calls[0].split(" = ")[0]
        for line in calls:
            operands = re.findall(r"(?:f32|bf16)\[[\d,]*\]", line.split("custom-call(")[1])
            assert operands[:3] == [f"f32[{SDAR_BOARDS},{rows},4096]", f"f32[{SDAR_BOARDS},{rows},512]", f"bf16[{SDAR_BOARDS},{rows},512]"], operands
            assert operands[5:8] == [f"f32[{rows},128]", f"f32[{rows},128]", bias], operands
        assert not re.search(r"f32\[%d,\d+,(?:64|128),(?:64|128)\]" % SDAR_BOARDS, text)  # no scores between the kernels


def test_the_ninth_blocks_step_compiles_at_published_widths_and_fits_one_chip(one_chip, compiled_for_tpu):
    """The whole step of ``sdar_trunk_train_b128`` from its configuration file: 128 boards of a clean and a noised copy, 16,384 tokens, the
    masked kernel pair on all five layers (never the plain one), the batch's two noise arrays as arguments (the step draws nothing: no
    random bits in it), the routed path on 128 tokens a board (131,072 slots a layer, no XLA pass over them), the denoiser under its own
    scope and the third term under ``loss``, no leaf held off row-major and no state argument relaid, nothing remade to fit, arguments and
    temporaries under the chip's 15.75 GiB with ``recompute_experts`` (3.19 + 7.05 GiB when this was written)."""
    import importlib
    import json
    import re
    from pathlib import Path

    config = json.loads((Path(__file__).resolve().parents[1] / "benchmark" / "configs" / "sdar-30b-a3b-trunk-train.json").read_text())
    assert config["train"]["batch"] == SDAR_BOARDS and config["train"]["recompute_experts"] is True
    trainer = importlib.import_module("benchmark.families.sdar_trunk").make_trainer(config)
    cfg = trainer.cfg
    assert (cfg.hidden, cfg.heads, cfg.kv_heads, cfg.head_dim, cfg.expert_width, cfg.experts, cfg.held, cfg.layers, cfg.block_length) == (2048, 32, 4, 128, 768, 128, (0, 8), 5, 4)
    on_chip = lambda tree: jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), tree)
    state = jax.eval_shape(trainer._init, jax.random.PRNGKey(0))
    assert sum(v.size for v in state.params.values()) == config["published"]["parameters_here"] == 284_743_515  # the configuration file's reckoning
    batch = {"planes": jnp.zeros((SDAR_BOARDS, 8, 8, 19)), "policy_target": jnp.zeros((SDAR_BOARDS, 4672)), "value_target": jnp.zeros((SDAR_BOARDS,)),
             "block_level": jnp.ones((SDAR_BOARDS, 16)), "square_masked": jnp.zeros((SDAR_BOARDS, 64), bool)}
    compiled = _held_as_the_trainer_holds_it(trainer, one_chip)._step_jit.lower(on_chip(state), on_chip(batch)).compile()
    text = compiled.as_text()
    assert not trainer._held and not _copies_of_state_arguments(text)
    calls = [line for line in text.splitlines() if 'custom_call_target="tpu_custom_call"' in line and "board_attention" in line]
    assert len(calls) == 10 and all("board_attention_blocks" in line.split(" = ")[0] for line in calls)  # five layers, forward and gradient, the masked pair alone
    assert sum("board_attention_blocks_grad" in line.split(" = ")[0] for line in calls) == 5
    for line in calls:  # both copies of a board side by side: 128 rows
        shapes = re.findall(r"(?:f32|bf16)\[[\d,]*\]", line.split("custom-call(")[1])
        assert shapes[:3] == ["f32[128,128,4096]", "f32[128,128,512]", "bf16[128,128,512]"] and shapes[5:8] == ["f32[128,128]", "f32[128,128]", "f32[192,128]"], shapes  # the mask laid twice along a pair's queries
    for phase in ("jvp(forward)", "transpose(jvp(forward))"):
        assert all(f"{phase}/layer0{i}.{part}/" in text for i in range(5) for part in ("attention", "router", "dispatch", "experts", "combine"))
        assert f"{phase}/denoise/" in text and f"{phase}/embed/" in text and ".shared/" not in text and ".dense/" not in text
    assert "jvp(loss)/denoise/" in text and "transpose(jvp(loss))/denoise/" in text
    assert "rng-bit-generator" not in text and "rng_bit_generator" not in text and "threefry" not in text  # the noise is the batch's
    assert not _xla_passes_over_slots(text, SDAR_BOARDS * 2 * trunk.SQUARES * cfg.experts_per_token)
    memory = compiled.memory_analysis()
    print("sdar step", memory.argument_size_in_bytes / 2 ** 30, memory.temp_size_in_bytes / 2 ** 30)
    assert 4.0 < (memory.argument_size_in_bytes + memory.temp_size_in_bytes) / 2 ** 30 < 12.5, (memory.argument_size_in_bytes / 2 ** 30, memory.temp_size_in_bytes / 2 ** 30)
    assert ".remat" not in text


# -- the tenth block (ouro) at its published widths: six layers walked four times over the same weights, an exit a pass ---------------------------

OURO_BOARDS = 32  # ouro_trunk_train_b32


def test_the_tenth_blocks_step_compiles_at_published_widths_and_fits_one_chip(one_chip, compiled_for_tpu):
    """The whole step of ``ouro_trunk_train_b32`` from its configuration file: 32 boards, 2,048 tokens a pass, the six layers walked
    ``total_ut_steps`` = 4 times over the SAME weights (24 calls of the plain attention pair each way, at a group of ONE without a norm: the
    one-head program; 24 of the gate kernel pair between the joined products: a gate weight of 44 MiB is over ``_FUSED_GATE_BYTES``), no
    routed layer (no router, dispatch, experts or combine scope, no grouped product), every layer's scopes under the same names whatever
    the pass, the exits' four scopes and the loss's, no leaf held off row-major and no state argument relaid, nothing remade to fit,
    arguments and temporaries under the chip's 15.75 GiB (3.45 + 5.95 GiB when this was written: what PERF.md quotes)."""
    import importlib
    import json
    import re
    from pathlib import Path

    config = json.loads((Path(__file__).resolve().parents[1] / "benchmark" / "configs" / "ouro-2.6b-trunk-train.json").read_text())
    assert config["train"]["batch"] == OURO_BOARDS
    trainer = importlib.import_module("benchmark.families.ouro_trunk").make_trainer(config)
    cfg = trainer.cfg
    assert (cfg.hidden, cfg.heads, cfg.kv_heads, cfg.head_dim, cfg.dense_width, cfg.layers, cfg.dense_layers, cfg.loop_steps, cfg.exit_threshold) == (
        2048, 16, None, 128, 5632, 6, 6, 4, 1.0) and not cfg.qk_norm and cfg.post_norms and cfg.routed_layers == 0
    on_chip = lambda tree: jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), tree)
    state = jax.eval_shape(trainer._init, jax.random.PRNGKey(0))
    assert sum(v.size for v in state.params.values()) == config["published"]["parameters_here"] == 308_599_375  # the configuration file's reckoning
    batch = {"planes": jnp.zeros((OURO_BOARDS, 8, 8, 19)), "policy_target": jnp.zeros((OURO_BOARDS, 4672)), "value_target": jnp.zeros((OURO_BOARDS,))}
    compiled = _held_as_the_trainer_holds_it(trainer, one_chip)._step_jit.lower(on_chip(state), on_chip(batch)).compile()
    text = compiled.as_text()
    assert not trainer._held and not _copies_of_state_arguments(text)
    passes = cfg.loop_steps * cfg.layers
    calls = [line.split(" = ")[0] for line in text.splitlines() if 'custom_call_target="tpu_custom_call"' in line]
    core = [name for name in calls if "board_attention" in name]
    assert len(core) == 2 * passes and sum("board_attention_grad" in name for name in core) == passes and not any("blocks" in name for name in core)
    assert sum("expert_gate_grad" in name for name in calls) == passes and sum("expert_gate" in name for name in calls) == 2 * passes
    assert len(calls) == 4 * passes  # and no other kernel: no grouped product, no row move
    for line in text.splitlines():  # the one-head program: q, k, v alike, 16 heads of 128 over 32 boards
        if 'custom_call_target="tpu_custom_call"' in line and "board_attention" in line.split(" = ")[0]:
            assert re.findall(r"(?:f32|bf16)\[[\d,]*\]", line.split("custom-call(")[1])[:3] == ["f32[32,64,2048]", "f32[32,64,2048]", "bf16[32,64,2048]"]
    for phase in ("jvp(forward)", "transpose(jvp(forward))"):
        assert all(f"{phase}/layer0{i}.{part}/" in text for i in range(6) for part in ("attention", "dense"))
        assert all(f"{phase}/{scope}/" in text for scope in ("embed", "final_norm", "policy_head", "value_head", "exit_gate"))
    assert "jvp(loss)/exit/" in text and "transpose(jvp(loss))/exit/" in text
    assert not re.search(r"layer\d\d\.(router|dispatch|experts|combine|shared)/", text) and "/while/" not in text  # no routed layer; the passes are no loop of XLA's
    memory = compiled.memory_analysis()
    print("ouro step", memory.argument_size_in_bytes / 2 ** 30, memory.temp_size_in_bytes / 2 ** 30)
    assert 4.0 < (memory.argument_size_in_bytes + memory.temp_size_in_bytes) / 2 ** 30 < 12.5, (memory.argument_size_in_bytes / 2 ** 30, memory.temp_size_in_bytes / 2 ** 30)
    assert ".remat" not in text
