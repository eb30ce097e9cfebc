"""Real-size compiles for a DESCRIBED TPU v5e (no chip attached).

The TPU compiler is installed here and compiles for a topology that is
described, not attached — so the main path's kernels are checked at
GPT-2 124M widths, and the serving programs at the benchmark's GPT-2 XL
shapes, on every PR at no chip time: what Mosaic or the partitioner
would refuse on the chip, it refuses here, and a copy of the KV pool
that the compiler would put into a program shows in its text.  A
compile that passes is not a chip run (``chip_smoke.py`` is).

Everything that touches the topology — the description itself, the
shardings, meshes and shapes built from it — lives in module-scoped,
non-autouse fixtures of THIS file and runs in this process only: one
process may load the TPU library at a time, and every xdist worker
imports every test file.
"""

import importlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding

from ray_tpu.inference.cache import BlockPool, PoolLayout
from ray_tpu.inference.decode import (make_chunk_prefill_fn,
                                      make_paged_decode_step,
                                      make_paged_step_chunk)
from ray_tpu.models import gpt
from ray_tpu.parallel.sharding import DEFAULT_LLM_RULES, spec_for

# the modules, not the same-named functions ray_tpu.ops re-exports
attention_mod = importlib.import_module("ray_tpu.ops.attention")
cache_mod = importlib.import_module("ray_tpu.inference.cache")
flash_mod = importlib.import_module("ray_tpu.ops.flash_attention")

QKV = (16, 12, 1024, 64)      # GPT-2 124M training attention, bf16


@pytest.fixture(scope="module")
def topo():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def for_tpu(topo):
    """Steer the backend-asking code onto its TPU branch (this process
    computes on CPU, so ``jax.default_backend()`` says "cpu"), with the
    persistent compile cache off: an entry written for a described chip
    cannot be read back without one, and warns on every later compile."""
    from jax.experimental.compilation_cache import compilation_cache
    mp = pytest.MonkeyPatch()
    mp.setattr(flash_mod, "_interpret_mode", lambda: False)
    mp.setattr(attention_mod, "on_tpu", lambda: True)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()
    mp.undo()


@pytest.fixture(scope="module")
def one_chip(topo, for_tpu):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def qkv_one_chip(one_chip):
    return jax.ShapeDtypeStruct(QKV, jnp.bfloat16, sharding=one_chip)


@pytest.fixture(scope="module")
def mesh_2x2(topo, for_tpu):
    return Mesh(np.array(topo.devices).reshape(2, 2), ("dp", "tp"))


def _compile(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, "no Mosaic kernel in the program"
    return text


def _sum_grad(attend):
    return jax.grad(lambda q, k, v: attend(q, k, v).astype(jnp.float32).sum(),
                    argnums=(0, 1, 2))


def test_flash_forward_compiles(qkv_one_chip):
    x = qkv_one_chip
    _compile(lambda q, k, v: flash_mod.flash_attention(q, k, v), x, x, x)


def test_flash_forward_backward_compiles(qkv_one_chip):
    x = qkv_one_chip
    _compile(_sum_grad(flash_mod.flash_attention), x, x, x)


def _flash_calls_by_computation(text):
    """{computation: [result type of each Mosaic call in it]} of a
    compiled program's text; a computation with none is left out."""
    calls, name = {}, None
    for line in text.splitlines():
        if not line.startswith(" "):
            m = re.match(r"(?:ENTRY )?%?([\w.\-]+) .*\{$", line)
            name = m.group(1) if m else None
        elif name and 'custom_call_target="tpu_custom_call"' in line:
            calls.setdefault(name, []).append(
                line.split(" = ", 1)[1].split(" custom-call(")[0])
    return calls


@pytest.mark.parametrize("remat_policy, n_calls", [("dots", 3), (None, 4)])
def test_step_holds_its_flash_calls_once(one_chip, remat_policy, n_calls):
    """``jax.grad(gpt.loss_fn)`` at 124M width, 2 layers.  Under
    ``remat_policy="dots"`` the layer keeps what the flash forward rule
    names, so the backward scan's body holds the two backward kernels
    and NO forward (the one call that writes a float32 logsumexp, one
    number a query row): three Mosaic calls a layer.  Full recompute
    (None) reads no name and runs the forward again: four, the
    control."""
    cfg = gpt.GPTConfig.gpt2_124m(n_layers=2, remat=True,
                                  remat_policy=remat_policy)
    params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        jax.eval_shape(lambda: gpt.init_params(cfg, jax.random.PRNGKey(0))))
    batch = {"tokens": jax.ShapeDtypeStruct((QKV[0], cfg.max_seq + 1),
                                            jnp.int32, sharding=one_chip)}
    calls = _flash_calls_by_computation(_compile(
        jax.grad(lambda p, b: gpt.loss_fn(p, b, cfg)), params, batch))
    bh = QKV[0] * QKV[1]
    # the forward is the call with a float32 result, the backward pair
    # gives bf16 only: dq, and (dk, dv) as a tuple
    is_fwd = [[f"f32[{bh},1,{QKV[2]}]" in c for c in body]
              for body in calls.values()]
    assert sorted(map(len, is_fwd)) == [1, n_calls - 1], calls
    fwd_scan, bwd_scan = sorted(is_fwd, key=len)
    assert fwd_scan == [True]
    assert sum(bwd_scan) == n_calls - 3, calls
    for body in calls.values():
        assert all(f"bf16[{bh},{QKV[2]},{QKV[3]}]" in c for c in body), calls


def test_attend_compiles_under_dp_tp_mesh(mesh_2x2):
    """The partitioner refuses a bare Mosaic call under a mesh
    ("cannot be automatically partitioned"); gpt._attend must hand it
    over per shard.  q/k/v sharded batch->dp, heads->tp.  The residuals
    the forward rule names are per shard then, and the layer's
    checkpoint policy still finds them: one forward call, not two."""
    cfg = gpt.GPTConfig.gpt2_124m(remat=True, remat_policy="dots")
    spec = spec_for(("batch", "heads", "seq", "kv"), DEFAULT_LLM_RULES,
                    mesh_2x2)
    x = jax.ShapeDtypeStruct(QKV, jnp.bfloat16,
                             sharding=NamedSharding(mesh_2x2, spec))
    text = _compile(_sum_grad(jax.checkpoint(
        lambda q, k, v: gpt._attend(q, k, v, cfg, mesh_2x2,
                                    DEFAULT_LLM_RULES),
        policy=gpt._checkpoint_policy(cfg.remat_policy))), x, x, x)
    # per shard: 16/2 batch rows x 12/2 heads
    assert "bf16[8,6,1024,64]" in text
    calls = sum(_flash_calls_by_computation(text).values(), [])
    assert len(calls) == 3 and sum("f32[" in c for c in calls) == 1, calls


def test_loss_grad_compiles_under_pp_dp_mesh(topo, for_tpu):
    """A pp mesh: the pipeline binds ``pp`` manual itself and the flash
    call nests inside it, manual over the remaining axes only.  Through
    ``gpt.loss_fn`` at 124M width, 4 layers (2 a stage)."""
    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("pp", "dp"))
    cfg = gpt.GPTConfig.gpt2_124m(n_layers=4, remat=True,
                                  remat_policy="dots")

    def on(logical, s):
        spec = spec_for(logical, DEFAULT_LLM_RULES, mesh)
        return jax.ShapeDtypeStruct(s.shape, s.dtype,
                                    sharding=NamedSharding(mesh, spec))

    params = jax.tree.map(
        on, gpt.param_logical_axes(cfg),
        jax.eval_shape(lambda: gpt.init_params(cfg, jax.random.PRNGKey(0))),
        is_leaf=lambda x: isinstance(x, tuple))
    batch = {"tokens": on(("batch", None), jax.ShapeDtypeStruct(
        (QKV[0], cfg.max_seq + 1), jnp.int32))}
    text = _compile(jax.value_and_grad(
        lambda p, b: gpt.loss_fn(p, b, cfg, mesh=mesh,
                                 rules=DEFAULT_LLM_RULES)), params, batch)
    # per shard: 4 microbatches of 16 rows, each halved over dp
    assert "bf16[2,12,1024,64]" in text


HBM = 15.75 * 2 ** 30         # what one v5e chip gives a program


def _on(sharding):
    def on_chip(shape, dtype, axes=None):       # one chip: axes unused
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
    return on_chip


def _pool_of(cfg, n_blocks, bs, on_chip):
    """The pool array as ``BlockPool`` itself shapes it (traced, never
    allocated), with its layout."""
    lay = jax.eval_shape(lambda: BlockPool(cfg, n_blocks, bs).k)
    return on_chip(lay.shape, lay.dtype), PoolLayout.of(cfg, lay)


def _params_of(cfg, place):
    """The parameters an engine hands its programs (float32 masters
    through ``gpt.serving_params``) as shapes, each put by
    ``place(shape, dtype, logical axes)``."""
    return jax.tree.map(
        lambda axes, s: place(s.shape, s.dtype, axes),
        gpt.param_logical_axes(cfg, served=True),
        jax.eval_shape(lambda: gpt.serving_params(
            gpt.init_params(cfg, jax.random.PRNGKey(0)), cfg)),
        is_leaf=lambda x: isinstance(x, tuple))


def _assert_pool_stays_put(compiled, lay, n_pools=2, own=()):
    """What ISSUE 27 bought, on the compiled program: (a) no ``copy``
    of K/V (bf16; the weights are f32) whose result is as large as one
    layer's share of a pool — of a pool, a slice, or a context, (b) the
    pools enter, ride the layer scan and leave in ONE layout, the plain
    row-major one, (c) arguments + scratch fit the chip.  ``own``:
    shapes of K/V the program is GIVEN in another layout, whose
    re-tiling is theirs and not the pool's."""
    # a custom call repeats its operands' shapes as CONSTRAINTS, untiled
    text = re.sub(r"operand_layout_constraints=\{.*?\}\}", "",
                  compiled.as_text())
    share = int(np.prod(lay.shape)) * 2 // lay.n_layers
    big = []
    for m in re.finditer(r"= \(?bf16\[([\d,]*)\]\S* copy(?:-start)?\(", text):
        n = int(np.prod([int(d) for d in m.group(1).split(",") if d] or [1]))
        if n * 2 >= share and m.group(1) not in own:
            big.append(m.group(0))
    assert not big, f"pool-sized copies in the program: {big}"
    pool = "bf16[" + ",".join(map(str, lay.shape)) + "]"
    layouts = set(re.findall(re.escape(pool) + r"\{([^}]*)\}", text))
    assert len(layouts) == 1 and layouts.pop().startswith("2,1,0"), layouts
    entry = re.search(r"entry_computation_layout=.*", text).group(0)
    assert entry.count(pool) >= 2 * n_pools         # in and out
    mem = compiled.memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert used < HBM, f"program needs {used / 2**30:.1f} GiB"
    # the donated pools are updated in place: no second pool in scratch
    assert mem.alias_size_in_bytes >= n_pools * int(np.prod(lay.shape)) * 2


def _entry_parameters(text):
    """The entry computation's parameters, as its layout lists them."""
    inside = re.search(r"entry_computation_layout=\{\((.*)\)->", text).group(1)
    parts, depth, at = [], 0, 0
    for i, ch in enumerate(inside):
        depth += (ch in "[{(") - (ch in "]})")
        if ch == "," and not depth:
            parts.append(inside[at:i].strip())
            at = i + 1
    return parts + [inside[at:].strip()]


def _assert_token_array_donated(compiled, rows):
    """ISSUE 55: the token each row feeds next, int32 ``[rows]``, is the
    program's operand before the packed array, resident on the device,
    and donated like the pools: the compiled program aliases it to a
    result (the loop launches the next pass on the array this one
    returns, with no copy and no transfer)."""
    text = compiled.as_text()
    params = [re.sub(r"^/\*index=\d+\*/", "", p)
              for p in _entry_parameters(text)]
    assert params[-2].startswith(f"s32[{rows}]"), params[-2:]
    # ``input_output_alias={ {2}: (16, {}, may-alias), .. }``: result
    # index -> (parameter, index in it, kind)
    head = text[:text.index("\n")]
    aliased = {int(n) for n in re.findall(
        r"\{[\d, ]*\}: \((\d+), \{[\d, ]*\}, \w+-alias\)",
        head[head.index("input_output_alias="):])}
    assert len(params) - 2 in aliased, (len(params), sorted(aliased))


@pytest.fixture(scope="module")
def xl(one_chip):
    """The serve cell's shapes: gpt2-xl, 32 rows, 768 + 1 blocks of 16,
    64-block tables (chipbench/configs/gpt2-xl.json)."""
    cfg = gpt.GPTConfig(d_model=1600, n_heads=25, n_layers=48, d_ff=6400)
    on_chip = _on(one_chip)
    pool, lay = _pool_of(cfg, 768, 16, on_chip)
    assert lay.shape == (48 * 769, 16, 1664)        # 1600 -> 13 x 128
    return cfg, on_chip, pool, lay


@pytest.fixture(scope="module")
def xl_compiled(xl):
    """``program -> `` the cell's decode step, chunk prefill or the
    program that runs both (``step_chunk``), compiled
    once with the served tree's shapes (``out_info``: its results'
    shapes instead)."""
    cfg, on_chip, pool, lay = xl
    rows, T = 32, cfg.max_seq // lay.block_size
    params = _params_of(cfg, on_chip)
    assert params["layers"]["w_up"].dtype == cfg.dtype
    assert params["wte"].dtype == jnp.float32       # added, THEN rounded
    feed = on_chip((rows,), jnp.int32)      # the rows' next tokens
    done = {}

    def compiled(program, out_info=False):
        if program not in done:
            if program == "decode":
                lowered = make_paged_decode_step(
                    cfg, block_size=lay.block_size, n_table=T).lower(
                    params, pool, pool, feed,
                    on_chip((rows, T + 3), jnp.int32))
            elif program == "chunk":
                lowered = make_chunk_prefill_fn(
                    cfg, chunk=32, block_size=lay.block_size,
                    n_table=T).lower(
                    params, pool, pool, feed,
                    on_chip((T + 32 + 3,), jnp.int32))
            else:                   # the two as ONE program (ISSUE 41)
                lowered = make_paged_step_chunk(
                    cfg, chunk=32, block_size=lay.block_size,
                    n_table=T).lower(
                    params, pool, pool, feed,
                    on_chip((rows * (T + 3) + T + 32 + 3,), jnp.int32))
            done[program] = (lowered.compile(), lowered.out_info)
        return done[program][int(out_info)]
    return compiled


def test_xl_decode_step_moves_no_pool(xl, xl_compiled):
    _assert_pool_stays_put(xl_compiled("decode"), xl[3])
    _assert_token_array_donated(xl_compiled("decode"), 32)


def test_xl_chunk_prefill_moves_no_pool(xl, xl_compiled):
    _assert_pool_stays_put(xl_compiled("chunk"), xl[3])
    _assert_token_array_donated(xl_compiled("chunk"), 32)


def test_xl_step_chunk_moves_no_pool(xl, xl_compiled):
    _assert_pool_stays_put(xl_compiled("step_chunk"), xl[3])
    _assert_token_array_donated(xl_compiled("step_chunk"), 32)


@pytest.mark.parametrize("program", ["chunk", "step_chunk"])
def test_xl_chunk_stays_on_the_packed_form(xl, xl_compiled, program):
    """XL's table is ONE key block (64 blocks of 16 = 1,024 keys), so a
    chunk's queries are derived onto the packed form whatever family's
    program asks (``decode.window_by_head``: the walk of one block IS
    the packed product, and packed keeps the lane trick for 25 heads of
    64): no window kernel in the programs, the gathered table there."""
    from ray_tpu.inference.decode import window_by_head
    cfg, _, _, lay = xl
    T = cfg.max_seq // lay.block_size
    assert T * lay.block_size == 1024 and not window_by_head(lay, T)
    assert window_by_head(lay, T + 1)
    text = xl_compiled(program).as_text()
    assert not _kernel_calls(text, "head_window_attention")
    assert re.search(rf"bf16\[(?:1,)?(?:{T},{lay.block_size}|1024),"
                     rf"{lay.width}\]\S* (?:gather|fusion)\(", text)


def test_xl_step_chunk_reads_each_layer_s_weights_once(xl, xl_compiled):
    """What ISSUE 41 bought: in the pass that holds a chunk and decoding
    rows, the 32 rows' tokens and the chunk's 32 are ONE window of the
    layer function, so each of a layer's four matrices is the operand
    of ONE product, 64 rows wide, and streams from HBM once a pass; the
    two programs it stands in for each read all of them for 32 rows."""
    cfg, lay = xl[0], xl[3]
    pool = "bf16[" + ",".join(map(str, lay.shape)) + "]"
    text = xl_compiled("step_chunk").as_text()
    body = re.search(r"body=(%[\w.]+)", next(
        line for line in text.splitlines() if " while(" in line
        and pool in line)).group(1)
    scan = text.split(f"\n{body} ", 1)[1].split("\n}\n", 1)[0]
    d, ff = cfg.d_model, cfg.d_ff
    for out in (3 * d, d, ff):
        wide = re.findall(rf"= bf16\[(?:1,)?64,{out}\]\S* (?:fusion|dot|"
                          rf"convolution)\(", scan)
        assert wide, f"no product [64, {out}] in the layer scan"
    # no product of the layer over one part of the window alone
    for out in (3 * d, ff):
        assert not re.findall(rf"bf16\[(?:1,)?32,(?:1,)?{out}\]", scan)
    # ... and the decode step's and the chunk program's products are
    # what this test would have seen there
    for program in ("decode", "chunk"):
        assert re.findall(rf"bf16\[(?:1,)?32,(?:1,)?{ff}\]",
                          xl_compiled(program).as_text())


def _kernel_calls(text, name="paged_decode_attention"):
    return [line.strip() for line in text.splitlines()
            if " custom-call(" in line and "tpu_custom_call" in line
            and line.strip().startswith(f"%{name}")]


@pytest.mark.parametrize("program", ["decode", "step_chunk"])
def test_xl_decode_step_walks_the_tables_in_one_kernel(xl, xl_compiled,
                                                       program):
    """What ISSUE 35 bought: the decode step gathers no row's table
    (32 rows x 64 columns of [16, 1664] blocks, one a pool a layer, were
    two thirds of the program) and holds the table-walking kernel ONCE,
    inside the layer scan, on both pools as stored.  So does the program
    that also runs a chunk (which gathers the chunk row's one table)."""
    lay = xl[3]
    text = xl_compiled(program).as_text()
    assert "bf16[2048,16,1664]" not in text
    assert "bf16[32,1024,1664]" not in text
    pool = "bf16[" + ",".join(map(str, lay.shape)) + "]"
    call, = _kernel_calls(text)
    assert call.count(pool) == 2
    body = re.search(r"body=(%[\w.]+)", next(
        line for line in text.splitlines() if " while(" in line
        and pool in line)).group(1)
    scan = text.split(f"\n{body} ", 1)[1].split("\n}\n", 1)[0]
    assert call in scan


@pytest.mark.parametrize("program", ["decode", "chunk", "step_chunk"])
def test_xl_program_casts_and_retiles_no_weights(xl, xl_compiled, program):
    """What ISSUE 30 bought: handed the served tree, a program holds no
    ``convert`` whose result is a stacked layer weight (from float32
    masters every decode and chunk program began with six, 6.1 GB read
    and 3.1 GB written, and kept the results as 2.97 GB of scratch) and
    no ``copy`` of the embedding table for the head."""
    cfg = xl[0]
    text = xl_compiled(program).as_text()
    stacked = {",".join(map(str, s.shape)) for s in jax.tree.leaves(
        jax.eval_shape(lambda: gpt.init_params(
            cfg, jax.random.PRNGKey(0))["layers"])) if len(s.shape) == 3}
    assert f"{cfg.n_layers},{cfg.d_model},{cfg.d_ff}" in stacked
    casts = [m.group(0) for m in re.finditer(
        r"= \(?bf16\[([\d,]*)\]\S* convert\(", text)
        if m.group(1) in stacked]
    assert not casts, f"stacked weights cast inside the program: {casts}"
    table = (f"{cfg.vocab_size},{cfg.d_model}",
             f"{cfg.d_model},{cfg.vocab_size}")
    copies = [m.group(0) for m in re.finditer(
        r"= \(?\w+\[([\d,]*)\]\S* copy(?:-start)?\(", text)
        if m.group(1) in table]
    assert not copies, f"the embedding re-tiled for the head: {copies}"
    assert xl_compiled(program).memory_analysis().temp_size_in_bytes < 5e8


@pytest.mark.parametrize("program, n, rows", [
    ("decode", 32, 32), ("chunk", 1, 32), ("step_chunk", 33, 33)])
def test_xl_program_returns_its_own_greedy_tokens(xl, xl_compiled, program,
                                                  n, rows):
    """What ISSUE 39 bought: beside the float32 logits, which stay on
    the device, each program hands back the argmax it took itself, as
    int32 (every row's from the step, the last real position's from the
    chunk): a greedy pass fetches 128 bytes where it fetched 6.4 MB and
    uploaded them again for an argmax program of its own.  The same
    compiled programs still move no pool and cast no weight (above)."""
    cfg = xl[0]
    logits, greedy, k, v, feed = xl_compiled(program, out_info=True)
    # (the step that runs a chunk: its 32 rows, then the chunk's last
    # real position, whose token a prompt's first is)
    assert (logits.dtype, logits.shape) == (jnp.float32,
                                            (rows, cfg.vocab_size))
    assert (greedy.dtype, greedy.shape) == (jnp.int32, (n,))
    assert k.shape == v.shape == xl[3].shape
    # ... and the token each of the 32 rows feeds next (ISSUE 55)
    assert (feed.dtype, feed.shape) == (jnp.int32, (32,))
    entry = re.search(r"entry_computation_layout=.*",
                      xl_compiled(program).as_text()).group(0)
    results = entry.split("->", 1)[1]
    assert f"s32[{n}]" in results \
        and f"f32[{rows},{cfg.vocab_size}]" in results
    # one packed int32 array in, behind the token array: no other
    # integer argument
    assert entry.split("->", 1)[0].count("s32[") == 2


def test_xl_write_blocks_moves_no_pool(xl):
    """The full-width prefill's table scatter: its scratch was as large
    as both pools, which is what held ``n_blocks`` at 768."""
    cfg, on_chip, pool, lay = xl
    T = cfg.max_seq // lay.block_size
    kv = on_chip((cfg.n_layers, cfg.n_heads, cfg.max_seq, cfg.head_dim),
                 cfg.dtype)
    compiled = cache_mod._write_blocks.lower(
        lay, pool, pool, on_chip((T,), jnp.int32), kv, kv).compile()
    _assert_pool_stays_put(compiled, lay,
                           own=(",".join(map(str, kv.shape)),))
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 30


@pytest.mark.parametrize("program", ["decode", "step_chunk"])
def test_paged_decode_step_keeps_its_pool_shard_under_dp_tp(mesh_2x2,
                                                            program):
    """tp=2: every device holds whole heads (6 of 12: 384 lanes, no
    padding) of every block, in the same row-major layout, and the
    program copies none of it; its attention kernel runs per shard.
    The step that also runs a chunk (ISSUE 41) the same."""
    cfg = gpt.GPTConfig.gpt2_124m()
    rows, bs = 8, 16
    n_table = cfg.max_seq // bs

    def on_mesh(shape, dtype, axes=None):
        spec = spec_for(axes or (None,) * len(shape), DEFAULT_LLM_RULES,
                        mesh_2x2)
        return jax.ShapeDtypeStruct(
            shape, dtype, sharding=NamedSharding(mesh_2x2, spec))

    lay = PoolLayout(cfg.n_layers, rows * n_table + 1, bs, cfg.n_heads,
                     cfg.head_dim, cache_mod.heads_shards(mesh_2x2))
    assert (lay.shards, lay.width) == (2, cfg.d_model)
    pool = on_mesh(lay.shape, cfg.dtype, cache_mod.POOL_AXES)
    if program == "decode":
        step = make_paged_decode_step(cfg, block_size=bs, n_table=n_table,
                                      mesh=mesh_2x2)
        packed = on_mesh((rows, n_table + 3), jnp.int32)
    else:
        step = make_paged_step_chunk(cfg, chunk=32, block_size=bs,
                                     n_table=n_table, mesh=mesh_2x2)
        packed = on_mesh((rows * (n_table + 3) + n_table + 32 + 3,),
                         jnp.int32)
    compiled = step.lower(_params_of(cfg, on_mesh), pool, pool,
                          on_mesh((rows,), jnp.int32), packed).compile()
    shard = PoolLayout(lay.n_layers, lay.n_rows, bs, cfg.n_heads // 2,
                       cfg.head_dim)
    assert shard.shape == (*lay.shape[:2], 384)
    _assert_pool_stays_put(compiled, shard)
    # the table-walking kernel per shard: its 4 of the 8 rows (dp), its
    # 6 heads' slice of both pools (tp)
    call, = _kernel_calls(compiled.as_text())
    assert call.startswith("%paged_decode_attention") \
        and " = f32[4,1,384]" in call
    assert call.count("bf16[" + ",".join(map(str, shard.shape)) + "]") == 2


def test_paged_decode_step_compiles_at_124m(one_chip):
    """One engine program at full width: the paged decode step over the
    default serving geometry (8 rows, 16-token blocks, 1024-token
    tables): Mosaic takes the table-walking kernel at 12 heads of 64 in
    768 lanes (no padding), the program leaves the pool where it is and
    fits the chip."""
    cfg = gpt.GPTConfig.gpt2_124m()
    rows, bs = 8, 16
    n_table = cfg.max_seq // bs
    on_chip = _on(one_chip)
    pool, lay = _pool_of(cfg, rows * n_table, bs, on_chip)
    assert lay.width == cfg.d_model
    step = make_paged_decode_step(cfg, block_size=bs, n_table=n_table)
    compiled = step.lower(
        _params_of(cfg, on_chip), pool, pool, on_chip((rows,), jnp.int32),
        on_chip((rows, n_table + 3), jnp.int32)).compile()
    _assert_pool_stays_put(compiled, lay)
    _assert_token_array_donated(compiled, rows)
    assert len(_kernel_calls(compiled.as_text())) == 1


# ---- the second model family: K/V blocks AND a recurrent state pool

@pytest.fixture(scope="module")
def hybrid_cell(one_chip):
    """The shapes of ``serve-granite-h-chat2k-r80``
    (chipbench/configs/granite-4.0-h-small-10L-e36.json): one period of
    published widths, 36 of 72 experts and half the vocabulary held, 64
    rows, 9,216 + 1 blocks of 16, 144-block tables."""
    from ray_tpu.models import hybrid
    cfg = hybrid.HybridConfig(vocab_size=50176, experts_held=(0, 36),
                              max_seq=2304)
    on_chip = _on(one_chip)
    rows, bs = 64, 16
    lay = PoolLayout(*cfg.kv_geometry[:1], 9216 + 1, bs,
                     *cfg.kv_geometry[1:])
    assert lay.shape == (9217, 16, 1024)        # ONE K/V layer, 8 x 128
    layers, conv, ssm = cfg.state_geometry
    params = jax.tree.map(
        lambda s: on_chip(s.shape, s.dtype),
        jax.eval_shape(lambda: hybrid.init_params(cfg,
                                                  jax.random.PRNGKey(0))))
    n_bytes = sum(int(np.prod(s.shape)) * s.dtype.itemsize
                  for s in jax.tree.leaves(params))
    assert 9.50e9 < n_bytes < 9.53e9            # 4,757 M bf16 parameters
    return (cfg, on_chip, params, on_chip(lay.shape, cfg.dtype), lay,
            on_chip((layers, rows, *conv), cfg.dtype),
            on_chip((layers, rows, *ssm), jnp.float32), rows)


def _assert_state_stays_put(compiled, ssm):
    """The state pool is updated in place like the K/V pools: never
    copied or re-laid-out whole, and donated through."""
    text = compiled.as_text()
    shape = "f32[" + ",".join(map(str, ssm.shape)) + "]"
    assert not re.findall(re.escape(shape) + r"\S* copy(?:-start)?\(", text)
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= int(np.prod(ssm.shape)) * 4
    # a stacked expert tensor sliced per layer cost 432 MB of scratch a
    # layer; per-layer arrays and in-place pools leave well under 1 GiB
    assert mem.temp_size_in_bytes < 2 ** 30


@pytest.fixture(scope="module")
def hybrid_decode_compiled(hybrid_cell):
    """The cell's decode step, compiled once."""
    from ray_tpu.inference.recurrent import make_recurrent_decode_step
    cfg, on_chip, params, pool, lay, conv, ssm, rows = hybrid_cell
    T = cfg.max_seq // lay.block_size
    step = make_recurrent_decode_step(cfg, block_size=lay.block_size,
                                      n_table=T)
    return step.lower(params, (pool, pool), (conv, ssm),
                      on_chip((rows,), jnp.int32),
                      on_chip((rows, T + 3), jnp.int32)).compile()


def test_hybrid_decode_step_fits_and_moves_no_pool(hybrid_cell,
                                                   hybrid_decode_compiled):
    _assert_pool_stays_put(hybrid_decode_compiled, hybrid_cell[4])
    _assert_state_stays_put(hybrid_decode_compiled, hybrid_cell[6])
    _assert_token_array_donated(hybrid_decode_compiled, hybrid_cell[7])


def test_hybrid_decode_updates_the_state_in_one_kernel_a_layer(
        hybrid_cell, hybrid_decode_compiled):
    """Mosaic takes the one-token kernel at the cell's shapes, once a
    Mamba layer, on the state pool AS STORED (operand and result): so
    the dense update and the second read of the state are gone from
    the program, and the benchmark's trace reduction, which labels an
    op by its text, still counts the kernel as the SSM mixer's."""
    from chipbench.scoped_trace import label_of
    cfg, ssm = hybrid_cell[0], hybrid_cell[6]
    text = hybrid_decode_compiled.as_text()
    pool = "f32[" + ",".join(map(str, ssm.shape)) + "]"
    assert pool == "f32[9,64,8192,128]"
    on_pool = [line for line in text.splitlines()
               if "tpu_custom_call" in line and " custom-call(" in line
               and pool in line]
    assert len(on_pool) == cfg.n_mamba == 9
    for line in on_pool:
        result, operands = line.split(" custom-call(", 1)
        assert pool in result and pool in operands
        assert label_of(line.strip()) == "mixer_ssm"
    fusions = [line for line in text.splitlines() if " fusion(" in line]
    assert not [f for f in fusions if f.split(" = ")[1].startswith(pool)]
    assert not re.findall(r"multiply_reduce_fusion\S* = f32\[64,8192\]", text)


def test_hybrid_decode_walks_the_tables_in_one_kernel(
        hybrid_cell, hybrid_decode_compiled):
    """The one K/V layer's tables (64 rows x 144 columns) are not
    gathered, and the kernel that walks them belongs to no mechanism
    the benchmark's trace reduction counts by its text."""
    from chipbench.scoped_trace import label_of
    text = hybrid_decode_compiled.as_text()
    assert "bf16[9216,16,1024]" not in text
    call, = _kernel_calls(text)
    assert label_of(call, (640, 2560)) == "other"


def test_hybrid_chunk_prefill_fits_and_moves_no_pool(hybrid_cell):
    from ray_tpu.inference.recurrent import make_recurrent_chunk_fn
    cfg, on_chip, params, pool, lay, conv, ssm, rows = hybrid_cell
    T = cfg.max_seq // lay.block_size
    chunk = make_recurrent_chunk_fn(cfg, chunk=cfg.ssm_chunk,
                                    block_size=lay.block_size, n_table=T)
    compiled = chunk.lower(
        params, (pool, pool), (conv, ssm), on_chip((rows,), jnp.int32),
        on_chip((T + cfg.ssm_chunk + 3,), jnp.int32)).compile()
    _assert_token_array_donated(compiled, rows)
    _assert_pool_stays_put(compiled, lay)
    _assert_state_stays_put(compiled, ssm)


# ---- the second layout of the hybrid layer function: single-mixer layers,
# ---- 8 B/C groups, relu^2 experts (chipbench's nemotron-3-nano cell)

def _no_expert_stack_is_copied(text, params):
    """No stacked expert tensor is copied, sliced or re-laid-out on its
    way to the grouped matmul: a slice of a stack was a 432 MB copy a layer
    (PR 29), and a ``w_in`` whose minor dim is no whole number of lane
    tiles is re-laid-out whole on every pass (660 MB a layer at 64 x
    2688 x 1856: ``ops/routed_experts.lanes``)."""
    stacks = {"bf16[" + ",".join(map(str, lp["ffn"][name].shape)) + "]"
              for lp in params["layers"] if "router" in lp.get("ffn", ())
              for name in ("w_in", "w_out")}
    assert len(stacks) == 2
    for shape in stacks:
        assert shape in text
        assert not re.findall(
            re.escape(shape) + r"\S* (?:copy|copy-start|transpose|slice|"
            r"dynamic-slice)\(", text), shape
        layouts = set(re.findall(re.escape(shape) + r"\{([\d,]*)", text))
        assert layouts == {"2,1,0"}, (shape, layouts)


def test_hybrid_decode_hands_the_grouped_matmul_no_copy_of_a_stack(
        hybrid_cell, hybrid_decode_compiled):
    _no_expert_stack_is_copied(hybrid_decode_compiled.as_text(),
                               hybrid_cell[2])


@pytest.fixture(scope="module")
def nano_cell(one_chip):
    """The shapes of ``serve-nemotron3-nano-reason1k-r80``, from the
    cell's own configuration file through its traffic kind's
    ``model_config``: 13 layers ``MEMEM*EMEMEM*`` at published widths,
    64 of 128 experts and half the vocabulary held, 64 rows, 12,288 + 1
    blocks of 16, 192-block tables."""
    import json
    import os
    from chipbench.traffic.open_loop_http_nemotron_h import model_config
    from ray_tpu.models import hybrid
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "chipbench", "configs",
                           "nemotron-3-nano-30b-a3b-13L-e64.json")) as f:
        config = json.load(f)
    cfg, _, held = model_config(config)
    assert held == (0, 64) and cfg.n_experts == 128
    assert (cfg.n_mamba, cfg.n_attention, len(cfg.sublayers)) == (6, 2, 13)
    on_chip = _on(one_chip)
    rows, bs = config["engine"]["max_slots"], 16
    lay = PoolLayout(*cfg.kv_geometry[:1], config["engine"]["n_blocks"] + 1,
                     bs, *cfg.kv_geometry[1:])
    assert lay.shape == (24578, 16, 256)        # TWO K/V layers, 2 x 128
    layers, conv, ssm = cfg.state_geometry
    params = jax.tree.map(
        lambda s: on_chip(s.shape, s.dtype),
        jax.eval_shape(lambda: hybrid.init_params(cfg,
                                                  jax.random.PRNGKey(0))))
    n_bytes = sum(int(np.prod(s.shape)) * s.dtype.itemsize
                  for s in jax.tree.leaves(params))
    # 3,926 M published bf16 parameters + the zero columns of five
    # stacked w_in (64 x 2688 x 64 each: 1856 -> 1920 lanes)
    assert n_bytes - 5 * 64 * 2688 * 64 * 2 == 7_852_040_704
    return (cfg, on_chip, params, on_chip(lay.shape, cfg.dtype), lay,
            on_chip((layers, rows, *conv), cfg.dtype),
            on_chip((layers, rows, *ssm), jnp.float32), rows,
            config["engine"])


@pytest.fixture(scope="module")
def nano_decode_compiled(nano_cell):
    from ray_tpu.inference.recurrent import make_recurrent_decode_step
    cfg, on_chip, params, pool, lay, conv, ssm, rows, _ = nano_cell
    T = cfg.max_seq // lay.block_size
    step = make_recurrent_decode_step(cfg, block_size=lay.block_size,
                                      n_table=T)
    return step.lower(params, (pool, pool), (conv, ssm),
                      on_chip((rows,), jnp.int32),
                      on_chip((rows, T + 3), jnp.int32)).compile()


def test_nano_decode_step_fits_and_moves_no_pool(nano_cell,
                                                 nano_decode_compiled):
    _assert_pool_stays_put(nano_decode_compiled, nano_cell[4])
    _assert_state_stays_put(nano_decode_compiled, nano_cell[6])
    _assert_token_array_donated(nano_decode_compiled, nano_cell[7])
    _no_expert_stack_is_copied(nano_decode_compiled.as_text(), nano_cell[2])


def test_nano_decode_updates_the_grouped_state_in_one_kernel_a_layer(
        nano_cell, nano_decode_compiled):
    """Mosaic takes the one-token kernel with EIGHT B/C groups (a row's
    ``[8, 128]`` B and C, a chunk of 128 state rows picking its group's
    row by its own index), once a Mamba layer, on the state pool as
    stored; the cell's label table counts it as the grouped SSM's, the
    first configuration's table does not know the shape."""
    from chipbench import nemotron_trace, scoped_trace
    cfg, ssm = nano_cell[0], nano_cell[6]
    text = nano_decode_compiled.as_text()
    pool = "f32[" + ",".join(map(str, ssm.shape)) + "]"
    assert pool == "f32[6,64,4096,128]"
    on_pool = [line for line in text.splitlines()
               if "tpu_custom_call" in line and " custom-call(" in line
               and pool in line]
    assert len(on_pool) == cfg.n_mamba == 6
    for line in on_pool:
        result, operands = line.split(" custom-call(", 1)
        assert pool in result and pool in operands
        assert line.strip().startswith("%ssd_step")
        assert nemotron_trace.label_of(line.strip()) == "grouped_ssm"
        assert scoped_trace.label_of(line.strip()) == "other"
    fusions = [line for line in text.splitlines() if " fusion(" in line]
    assert not [f for f in fusions if f.split(" = ")[1].startswith(pool)]


def test_nano_decode_walks_the_tables_with_sixteen_queries_a_kv_head(
        nano_cell, nano_decode_compiled):
    """The paged-attention kernel at 256 stored lanes and 16 query heads
    a K/V head (it had run at 25 x 64 and 8 x 128 lanes): once an
    attention layer, no table gathered."""
    from chipbench import nemotron_trace
    text = nano_decode_compiled.as_text()
    assert "bf16[12288,16,256]" not in text
    calls = _kernel_calls(text)
    assert len(calls) == nano_cell[0].n_attention == 2
    marks = nemotron_trace.marks_of(
        {"num_experts_per_tok": 6, "n_routed_experts": 128,
         "mamba_num_heads": 64, "mamba_head_dim": 64, "n_groups": 8}, 64,
        128)
    assert all(nemotron_trace.label_of(c, marks) == "other" for c in calls)


def test_nano_chunk_prefill_fits_and_moves_no_pool(nano_cell):
    from ray_tpu.inference.recurrent import make_recurrent_chunk_fn
    cfg, on_chip, params, pool, lay, conv, ssm, rows, engine = nano_cell
    T = cfg.max_seq // lay.block_size
    C = engine["prefill_chunk"]
    assert C == cfg.ssm_chunk == 128
    chunk = make_recurrent_chunk_fn(cfg, chunk=C, block_size=lay.block_size,
                                    n_table=T)
    compiled = chunk.lower(params, (pool, pool), (conv, ssm),
                           on_chip((rows,), jnp.int32),
                           on_chip((T + C + 3,), jnp.int32)).compile()
    _assert_token_array_donated(compiled, rows)
    _assert_pool_stays_put(compiled, lay)
    _assert_state_stays_put(compiled, ssm)
    _no_expert_stack_is_copied(compiled.as_text(), params)


def test_grouped_matmul_is_one_kernel_at_both_layouts(hybrid_decode_compiled,
                                                      nano_decode_compiled):
    """``routed_experts.grouped_matmul`` is the Pallas grouped matmul at
    both published layouts, twice an experts sublayer: the first's 4096
    x 1536 / 768 x 4096 experts (ten sublayers) and the second's 2688 x
    1920 / 1856 x 2688 (five); the compiler's own kernel, which read 50
    % and 12 % of the roofline there, is in neither program."""
    def calls(compiled, name):
        return [line for line in compiled.as_text().splitlines()
                if " custom-call(" in line and "tpu_custom_call" in line
                and line.strip().startswith(name)]
    assert len(calls(hybrid_decode_compiled, "%gmm")) == 20
    assert len(calls(nano_decode_compiled, "%gmm")) == 10
    for compiled in (hybrid_decode_compiled, nano_decode_compiled):
        assert not calls(compiled, "%ragged-dot")


# ---- the two programs of a pass as ONE, for the layouts whose every
# ---- sublayer kind takes a window in two parts (ISSUE 46)

@pytest.fixture(scope="module")
def fused_cells(hybrid_cell, nano_cell):
    """``name ->`` (cfg, params, layout, ssm pool, rows, chunk, the fused
    program compiled once at the cell's shapes)."""
    from ray_tpu.inference.recurrent import (has_step_chunk,
                                             make_recurrent_step_chunk)
    cells = {"granite": (*hybrid_cell[:8], hybrid_cell[0].ssm_chunk),
             "nano": (*nano_cell[:8], nano_cell[8]["prefill_chunk"])}
    done = {}

    def compiled(name):
        if name not in done:
            cfg, on_chip, params, pool, lay, conv, ssm, rows, C = cells[name]
            assert has_step_chunk(cfg)
            T = cfg.max_seq // lay.block_size
            fused = make_recurrent_step_chunk(
                cfg, chunk=C, block_size=lay.block_size, n_table=T)
            args = (params, (pool, pool), (conv, ssm),
                    on_chip((rows,), jnp.int32),
                    on_chip((rows * (T + 3) + T + C + 3,), jnp.int32))
            done[name] = (cfg, params, lay, ssm, rows, C,
                          fused.lower(*args).compile(),
                          jax.make_jaxpr(fused)(*args))
        return done[name]
    return compiled


@pytest.mark.parametrize("name", ["granite", "nano"])
def test_hybrid_step_chunk_fits_and_moves_no_pool(fused_cells, name):
    """The K/V pools and the state pool ride the fused program as they
    ride the two: in place, in one layout, never copied — though the
    state pool now has TWO readers a layer (the one-token kernel and the
    slice of the chunk's row) — and no expert stack is re-laid out."""
    cfg, params, lay, ssm, rows, C, compiled, _ = fused_cells(name)
    _assert_pool_stays_put(compiled, lay)
    _assert_state_stays_put(compiled, ssm)
    _assert_token_array_donated(compiled, rows)
    _no_expert_stack_is_copied(compiled.as_text(), params)


@pytest.mark.parametrize("name", ["granite", "nano"])
def test_hybrid_step_chunk_walks_the_chunk_row_s_table(fused_cells, name):
    """Both cells' tables are more than one key block (2,304 and 3,072
    keys), so since PR 53 the chunk part of the fused window walks its
    row's table head by head: one ``head_window_attention`` call an
    attention layer beside the one-token kernel's, no gather of the
    table, no float32 scores of its span — and each cell's trace table
    leaves the kernel in ``other``, where the packed form's time lay."""
    from chipbench import nemotron_trace, scoped_trace
    from ray_tpu.inference.decode import window_by_head
    cfg, params, lay, ssm, rows, C, compiled, _ = fused_cells(name)
    assert window_by_head(lay, cfg.max_seq // lay.block_size)
    text = compiled.as_text()
    calls = _kernel_calls(text, "head_window_attention")
    assert len(calls) == len(_kernel_calls(text)) == cfg.n_attention
    assert f"bf16[1,{cfg.max_seq},{lay.width}]" not in text
    assert f"bf16[{cfg.max_seq},{lay.width}]" not in text
    _no_table_span_by_heads(text, cfg, lay, {"max_seq": cfg.max_seq})
    for line in calls:
        assert scoped_trace.label_of(line, (rows, C)) == "other"
        assert nemotron_trace.label_of(line) == "other"


@pytest.mark.parametrize("name", ["granite", "nano"])
def test_hybrid_step_chunk_reads_each_layer_s_weights_once(fused_cells,
                                                           name):
    """What ISSUE 46 bought: the rows' tokens and the chunk's are ONE
    window of the layer function, so every matrix of a layer is named by
    ONE product, ``rows + chunk`` rows wide (the experts' stacks by one
    grouped matmul each over the window's assignments), and streams from
    HBM once a pass; only the recurrences run a part at a time, the
    one-token kernel on the pool as stored."""
    cfg, params, lay, ssm, rows, C, compiled, traced = fused_cells(name)
    text = compiled.as_text()
    # as traced: every matrix of every layer is the operand of ONE
    # equation (the parameters are the program's first inputs)
    program = traced.jaxpr.eqns[0].params["jaxpr"].jaxpr
    leaves = jax.tree_util.tree_leaves_with_path(params)
    matrices = 0
    for (path, leaf), var in zip(leaves, program.invars):
        where = jax.tree_util.keystr(path)
        if "layers" in where and leaf.ndim >= 2 and "conv_w" not in where:
            matrices += 1
            assert sum(var in eqn.invars for eqn in program.eqns) == 1, where
    assert matrices == sum(
        w.ndim >= 2 and k != "conv_w" for lp in params["layers"]
        for sub in lp.values() for k, w in sub.items()) >= 40
    # the projections are as wide as the whole window
    w = rows + C
    d = cfg.d_model
    assert re.findall(
        rf"bf16\[(?:1,)?{w},{d}\]\S* (?:fusion|dot|convolution)\(", text)
    for part in (rows, C):
        assert not re.findall(
            rf"bf16\[(?:1,)?{part},{d}\]\S* (?:dot|convolution)\(", text)
    # ... and evaluated ONCE: a Mamba layer's ``in_proj`` product has six
    # consumers in the two parts, and without its barrier the compiler
    # computes it again for most of them (25 evaluations in granite's 9
    # layers, the weights read each time: 4 ms of 29 on the chip)
    wide = cfg.ssm_inner + cfg.conv_channels + cfg.ssm_heads
    products = re.findall(
        rf"^\s*%\S+ = bf16\[(?:1,)?{w},{wide}\]\S* fusion\(", text, re.M)
    assert len(products) == cfg.n_mamba, len(products)
    # the recurrences: one one-token kernel a Mamba layer, on the pool
    pool = "f32[" + ",".join(map(str, ssm.shape)) + "]"
    on_pool = [line for line in text.splitlines()
               if "tpu_custom_call" in line and " custom-call(" in line
               and pool in line]
    assert len(on_pool) == cfg.n_mamba
    gmm = [line for line in text.splitlines()
           if " custom-call(" in line and "tpu_custom_call" in line
           and line.strip().startswith("%gmm")]
    assert len(gmm) == 2 * sum(k == "experts" for _, k in cfg.sublayers)
    assert len(_kernel_calls(text)) == cfg.n_attention


# ---------------------------------------------------------------------------
# the latent-attention layout: ONE latent pool, the one-token kernel with
# the up-projection absorbed, the window form a key block at a time


@pytest.fixture(scope="module")
def latent_cell(one_chip):
    """The shapes of ``serve-deepseek-v2-docqa8k-r80``, from the cell's
    own configuration file through its traffic kind's ``model_config``:
    7 layers (the first dense) at published widths, 20 of 160 experts
    and an eighth of the vocabulary held, 32 rows, 28,672 + 1 blocks of
    16 x 640 lanes, 592-block tables."""
    import json
    import os
    from chipbench.traffic.open_loop_http_deepseek_v2 import model_config
    from ray_tpu.models import hybrid
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "chipbench", "configs",
                           "deepseek-v2-7L-e20.json")) as f:
        config = json.load(f)
    cfg, _, held = model_config(config)
    engine = config["engine"]
    on_chip = _on(one_chip)
    lay = PoolLayout(*cfg.kv_geometry[:1], engine["n_blocks"] + 1,
                     engine["kv_block_size"], *cfg.kv_geometry[1:], 1,
                     cfg.value_lanes)
    assert lay.shape == (7 * 28673, 16, 640)
    params = jax.tree.map(
        lambda s: on_chip(s.shape, s.dtype),
        jax.eval_shape(lambda: hybrid.init_params(cfg,
                                                  jax.random.PRNGKey(0))))
    return cfg, on_chip, params, on_chip(lay.shape, cfg.dtype), lay, engine


def _latent_program(latent_cell, which):
    from ray_tpu.inference.recurrent import (make_recurrent_chunk_fn,
                                             make_recurrent_decode_step)
    cfg, on_chip, params, pool, lay, engine = latent_cell
    T = -(-engine["max_seq"] // lay.block_size)
    if which == "step":
        fn = make_recurrent_decode_step(cfg, block_size=lay.block_size,
                                        n_table=T)
        packed = on_chip((engine["max_slots"], T + 3), jnp.int32)
    else:
        C = engine["prefill_chunk"]
        fn = make_recurrent_chunk_fn(cfg, chunk=C,
                                     block_size=lay.block_size, n_table=T)
        packed = on_chip((T + C + 3,), jnp.int32)
    return fn.lower(params, (pool,), (),
                    on_chip((engine["max_slots"],), jnp.int32),
                    packed).compile()


def _no_table_span_by_heads(text, cfg, lay, engine):
    """No array holds the table's span of keys (or the cache's width in
    positions) beside the 128 heads: scores or decompressed K/V of a
    whole row."""
    span = -(-engine["max_seq"] // lay.block_size) * lay.block_size
    for shape in set(re.findall(r"(?:bf16|f32)\[([\d,]+)\]", text)):
        dims = [int(d) for d in shape.split(",")]
        assert not (cfg.n_heads in dims and (
            span in dims or engine["max_seq"] in dims)), shape


@pytest.mark.parametrize("which", ["step", "chunk"])
def test_latent_programs_fit_and_move_no_pool(latent_cell, which):
    cfg, _, params, _, lay, engine = latent_cell
    compiled = _latent_program(latent_cell, which)
    _assert_pool_stays_put(compiled, lay, n_pools=1)
    text = compiled.as_text()
    _no_table_span_by_heads(text, cfg, lay, engine)
    _no_expert_stack_is_copied(text, params)
    # no weight of the latent mixer is re-laid out a pass (the fused
    # W_qb / W_kvb were: 75 + 33 MB of copies a layer)
    big = [m.group(1) for m in re.finditer(
        r"= \(?bf16\[([\d,]*)\]\S* copy\(", text)
        if np.prod([int(d) for d in m.group(1).split(",")]) >= 8e6]
    if which == "chunk":
        # the decode step's layout of W_uk / W_uv (heads, latent, lanes)
        # is not the decompression's: the chunk program turns each ONCE
        # a layer, outside its loop over key blocks (2 x 16.8 MB of the
        # ~0.3 GB a layer's other weights stream anyway)
        assert big.count("128,512,128") <= 2 * cfg.n_latent
        # ... and the window's rotated queries, an activation, are
        # brought heads-first for the kernel (16 MB a layer)
        rope_q = f"128,{engine['prefill_chunk']},64"
        big = [b for b in big if b not in ("128,512,128", rope_q)]
    assert not big, big
    calls = _kernel_calls(text, "latent_decode_attention")
    if which == "chunk":
        # the window kernel once a layer, inside the loop over key blocks
        assert not calls
        assert len(_kernel_calls(text, "latent_window_attention")) \
            == cfg.n_latent
        return
    # ONE one-token latent kernel a layer, the pool its operand as stored
    assert len(calls) == cfg.n_latent == 7
    pool = "bf16[" + ",".join(map(str, lay.shape)) + "]"
    from chipbench import deepseek_v2_trace
    marks = deepseek_v2_trace.marks_of(
        {"num_experts_per_tok": 6, "n_routed_experts": 160,
         "num_attention_heads": 128, "qk_nope_head_dim": 128,
         "v_head_dim": 128}, engine["max_slots"], engine["prefill_chunk"])
    for line in calls:
        assert pool in line.split(" custom-call(", 1)[1]
        assert "bf16[32,128,512]" in line.split(" custom-call(", 1)[0]
        assert deepseek_v2_trace.label_of(line, marks) \
            == "latent_decode_attention"


# the olmo_hybrid layout: a matrix state a row beside 30 K/V heads of 128
# lanes; the one-token delta-rule kernel on the state pool as stored, the
# window form's head-wise attention a key block at a time


@pytest.fixture(scope="module")
def olmo_cell(one_chip):
    """The shapes of ``serve-olmo-hybrid-doc3k-r80``, from the cell's
    own configuration file: 16 layers (12 linear : 4 full) at published
    widths, the whole vocabulary, 32 rows, 4,096 + 1 blocks of 16 x
    3,840 lanes, 536-block tables."""
    import json
    import os
    from ray_tpu.models import hybrid
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "chipbench", "configs",
                           "olmo-hybrid-7b-16L.json")) as f:
        config = json.load(f)
    engine = config["engine"]
    cfg = hybrid.HybridConfig.from_published(config,
                                             max_seq=engine["max_seq"])
    on_chip = _on(one_chip)
    lay = PoolLayout(*cfg.kv_geometry[:1], engine["n_blocks"] + 1,
                     engine["kv_block_size"], *cfg.kv_geometry[1:])
    assert lay.shape == (4 * 4097, 16, 3840)
    layers, conv, matrix = cfg.state_geometry
    rows = engine["max_slots"]
    params = jax.tree.map(
        lambda s: on_chip(s.shape, s.dtype),
        jax.eval_shape(lambda: hybrid.init_params(cfg,
                                                  jax.random.PRNGKey(0))))
    n_bytes = sum(int(np.prod(s.shape)) * s.dtype.itemsize
                  for s in jax.tree.leaves(params))
    assert 8.19e9 < n_bytes < 8.21e9            # 4,101 M bf16 parameters
    return (cfg, on_chip, params, on_chip(lay.shape, cfg.dtype), lay,
            on_chip((layers, rows, *conv), cfg.dtype),
            on_chip((layers, rows, *matrix), jnp.float32), engine)


def _olmo_program(olmo_cell, which, traced=False):
    from ray_tpu.inference.recurrent import (make_recurrent_chunk_fn,
                                             make_recurrent_decode_step,
                                             make_recurrent_step_chunk)
    cfg, on_chip, params, pool, lay, conv, matrix, engine = olmo_cell
    T = -(-engine["max_seq"] // lay.block_size)
    rows, C = engine["max_slots"], engine["prefill_chunk"]
    if which == "step":
        fn = make_recurrent_decode_step(cfg, block_size=lay.block_size,
                                        n_table=T)
        packed = on_chip((rows, T + 3), jnp.int32)
    elif which == "chunk":
        fn = make_recurrent_chunk_fn(cfg, chunk=C,
                                     block_size=lay.block_size, n_table=T)
        packed = on_chip((T + C + 3,), jnp.int32)
    else:
        fn = make_recurrent_step_chunk(cfg, chunk=C,
                                       block_size=lay.block_size, n_table=T)
        packed = on_chip((rows * (T + 3) + T + C + 3,), jnp.int32)
    args = (params, (pool, pool), (conv, matrix),
            on_chip((rows,), jnp.int32), packed)
    if traced:
        return jax.make_jaxpr(fn)(*args)
    return fn.lower(*args).compile()


@pytest.mark.parametrize("which", ["step", "chunk"])
def test_olmo_programs_fit_and_move_no_pool(olmo_cell, which):
    """Both programs compile for the described chip at the published
    widths, fit, and re-lay neither K/V pool nor the state pool out."""
    from chipbench import olmo_hybrid_trace
    cfg, _, _, _, lay, _, matrix, engine = olmo_cell
    compiled = _olmo_program(olmo_cell, which)
    _assert_pool_stays_put(compiled, lay)
    _assert_state_stays_put(compiled, matrix)
    text = compiled.as_text()
    state = "f32[" + ",".join(map(str, matrix.shape)) + "]"
    assert state == "f32[12,32,96,5760]"
    # no array holds the table's span of keys beside the 30 heads:
    # scores of a whole row
    span = -(-engine["max_seq"] // lay.block_size) * lay.block_size
    for shape in set(re.findall(r"(?:bf16|f32)\[([\d,]+)\]", text)):
        dims = [int(d) for d in shape.split(",")]
        assert not (cfg.n_heads in dims and span in dims), shape
    marks = olmo_hybrid_trace.marks_of(cfg_keys(cfg), engine["max_slots"],
                                       engine["prefill_chunk"])
    steps = _kernel_calls(text, "delta_step")
    if which == "chunk":
        assert not steps
        # the window kernel once a full-attention layer, inside the loop
        # over key blocks; no gather of the table
        calls = _kernel_calls(text, "head_window_attention")
        assert len(calls) == cfg.n_attention == 4
        assert f"bf16[{span},3840]" not in text
        for line in calls:
            assert olmo_hybrid_trace.label_of(line, marks) \
                == "window_attention"
        # the delta rule's window kernel once a linear layer, on THIS
        # layer's rows' state (never the pool), credited to the label
        # the benchmark sums
        windows = _kernel_calls(text, "delta_window")
        assert len(windows) == cfg.n_linear == 12
        for line in windows:
            assert state not in line
            assert olmo_hybrid_trace.label_of(line, marks) \
                == "mixer_linear_attention"
        # no loop carries a row's matrix state from block to block
        for line in text.splitlines():
            if " while(" in line:
                assert not re.search(r"f32\[(?:\d+,)*(?:30,96,192|96,5760)\]",
                                     line), line
        return
    # ONE one-token kernel a linear layer, the pool its operand AND its
    # result as stored
    assert len(steps) == cfg.n_linear == 12
    for line in steps:
        result, operands = line.split(" custom-call(", 1)
        assert state in result and state in operands
        assert olmo_hybrid_trace.label_of(line, marks) == "delta_step"
    # the one-token attention kernel at 3,840 stored lanes, once a layer
    assert len(_kernel_calls(text)) == cfg.n_attention


def test_olmo_step_chunk_fits_moves_no_pool_and_reads_weights_once(
        olmo_cell):
    """What ISSUE 58 bought: the delta rule takes a window in two parts,
    so the pass that holds a chunk and decoding rows is ONE program for
    this layout too.  Compiled for the described chip at the cell's
    shapes it fits, moves neither K/V pool nor the state pool (which has
    TWO readers a layer now: the one-token kernel, then the slice of the
    chunk's row, taken from the pool the kernel left), names every
    matrix of every layer in ONE product ``rows + chunk`` lanes wide,
    and runs the recurrence a part at a time: one ``delta_step`` a
    linear layer on the pool as stored, one ``delta_window`` on the
    chunk row's own state."""
    from chipbench import olmo_hybrid_trace
    from ray_tpu.inference.recurrent import has_step_chunk
    cfg, _, params, _, lay, _, matrix, engine = olmo_cell
    assert has_step_chunk(cfg)
    rows, C = engine["max_slots"], engine["prefill_chunk"]
    compiled = _olmo_program(olmo_cell, "step_chunk")
    _assert_pool_stays_put(compiled, lay)
    _assert_state_stays_put(compiled, matrix)
    _assert_token_array_donated(compiled, rows)
    text = compiled.as_text()
    state = "f32[" + ",".join(map(str, matrix.shape)) + "]"
    marks = olmo_hybrid_trace.marks_of(cfg_keys(cfg), rows, C)
    steps = _kernel_calls(text, "delta_step")
    assert len(steps) == cfg.n_linear == 12
    for line in steps:
        result, operands = line.split(" custom-call(", 1)
        assert state in result and state in operands
        assert olmo_hybrid_trace.label_of(line, marks) == "delta_step"
    windows = _kernel_calls(text, "delta_window")
    assert len(windows) == cfg.n_linear
    for line in windows:
        assert state not in line and "f32[1,30,96,192]" in line
        assert olmo_hybrid_trace.label_of(line, marks) \
            == "mixer_linear_attention"
    # both forms of attention a full layer: the rows walk their tables
    # in the one-token kernel, the chunk's queries their row's key blocks
    assert len(_kernel_calls(text)) == cfg.n_attention == 4
    assert len(_kernel_calls(text, "head_window_attention")) == 4
    # as traced: every matrix of every layer is the operand of ONE
    # equation (the parameters are the program's first inputs)
    program = _olmo_program(olmo_cell, "step_chunk",
                            traced=True).jaxpr.eqns[0].params["jaxpr"].jaxpr
    leaves = jax.tree_util.tree_leaves_with_path(params)
    matrices = 0
    for (path, leaf), var in zip(leaves, program.invars):
        where = jax.tree_util.keystr(path)
        if "layers" in where and leaf.ndim >= 2 and "conv_w" not in where:
            matrices += 1
            assert sum(var in eqn.invars for eqn in program.eqns) == 1, where
    assert matrices == 12 * 6 + 4 * 4       # wqkv wg wab wo | wqkv wo, MLP
    # the projections are as wide as the whole window, evaluated once a
    # layer (``wqkv``'s product has consumers in both parts)
    w, d = rows + C, cfg.d_model
    wide = 2 * cfg.lin_heads * cfg.lin_key_dim \
        + cfg.lin_heads * cfg.lin_value_dim
    assert wide == 11520
    products = [line for line in re.findall(
        rf"^\s*%\S+ = bf16\[(?:1,)?{w},{wide}\]\S* (?:dot|convolution)"
        rf"\(.*$", text, re.M) if "mixer_linear_proj" in line]
    assert len(products) == cfg.n_linear, len(products)
    for part in (rows, C):
        assert not re.findall(
            rf"bf16\[(?:1,)?{part},{d}\]\S* (?:dot|convolution)\(", text)


def cfg_keys(cfg):
    """The published keys ``olmo_hybrid_trace.marks_of`` reads."""
    return {"hidden_size": cfg.d_model,
            "linear_num_value_heads": cfg.lin_heads,
            "linear_key_head_dim": cfg.lin_key_dim,
            "linear_value_head_dim": cfg.lin_value_dim,
            "num_attention_heads": cfg.n_heads}


# the afmoe layout (Trinity): window and full attention layers over TWO
# groups of K/V pools, each walked by the one-token kernel and by the
# head-wise window kernel; the window layers' walks start behind the
# window


@pytest.fixture(scope="module")
def afmoe_cell(one_chip):
    """The shapes of ``serve-trinity-large-mixlen32k-r80``, from the
    cell's own configuration file: 5 layers (4 window : 1 full) at
    published widths, 32 of 256 experts, an eighth of the vocabulary,
    32 rows, two tables of 520 blocks of 64 a row."""
    import json
    import os
    from chipbench.traffic.open_loop_http_afmoe import model_config
    from ray_tpu.models import hybrid
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "chipbench", "configs",
                           "trinity-large-preview-5L-e32.json")) as f:
        config = json.load(f)
    engine = config["engine"]
    cfg, _, _ = model_config(config)
    assert (cfg.n_window, cfg.n_attention, cfg.window) == (4, 1, 4096)
    on_chip = _on(one_chip)
    bs = engine["kv_block_size"]
    full = PoolLayout(1, engine["n_blocks"] + 1, bs, *cfg.kv_geometry[1:])
    within = PoolLayout(4, engine["n_window_blocks"] + 1, bs,
                        *cfg.window_geometry[1:3])
    assert full.shape == (4097, 64, 1024)
    assert within.shape == (4 * 2625, 64, 1024)
    params = jax.tree.map(
        lambda s: on_chip(s.shape, s.dtype),
        jax.eval_shape(lambda: hybrid.init_params(cfg,
                                                  jax.random.PRNGKey(0))))
    n_bytes = sum(int(np.prod(s.shape)) * s.dtype.itemsize
                  for s in jax.tree.leaves(params))
    assert 8.6e9 < n_bytes < 8.7e9              # 4,322 M bf16 parameters
    pools = tuple(on_chip(lay.shape, cfg.dtype)
                  for lay in (full, full, within, within))
    return cfg, on_chip, params, pools, full, within, engine


def _afmoe_program(afmoe_cell, which):
    from ray_tpu.inference.recurrent import (make_recurrent_chunk_fn,
                                             make_recurrent_decode_step)
    cfg, on_chip, params, pools, full, _, engine = afmoe_cell
    T = -(-engine["max_seq"] // full.block_size)
    if which == "step":
        fn = make_recurrent_decode_step(cfg, block_size=full.block_size,
                                        n_table=T)
        packed = on_chip((engine["max_slots"], 2 * T + 3), jnp.int32)
    else:
        C = engine["prefill_chunk"]
        fn = make_recurrent_chunk_fn(cfg, chunk=C,
                                     block_size=full.block_size, n_table=T)
        packed = on_chip((2 * T + C + 3,), jnp.int32)
    return fn.lower(params, pools, (),
                    on_chip((engine["max_slots"],), jnp.int32),
                    packed).compile()


@pytest.mark.parametrize("which", ["step", "chunk"])
def test_afmoe_programs_fit_and_move_neither_group_of_pools(afmoe_cell,
                                                            which):
    """Both programs compile for the described chip at the published
    widths and the cell's sizes, fit, and re-lay out no pool of either
    group; each attention layer is ONE kernel call on its own group's
    pools (decode) or one call inside its loop over key blocks (chunk),
    and nothing holds a row's whole table of keys."""
    from chipbench import afmoe_trace
    cfg, _, _, _, full, within, engine = afmoe_cell
    compiled = _afmoe_program(afmoe_cell, which)
    _assert_pool_stays_put(compiled, full)
    _assert_pool_stays_put(compiled, within)
    text = compiled.as_text()
    span = -(-engine["max_seq"] // full.block_size) * full.block_size
    for shape in set(re.findall(r"(?:bf16|f32)\[([\d,]+)\]", text)):
        dims = [int(d) for d in shape.split(",")]
        assert not (cfg.n_heads in dims and span in dims), shape
        assert not (span in dims and full.width in dims), shape
    marks = afmoe_trace.marks_of(engine, full.shape, within.shape)
    if which == "chunk":
        calls = _kernel_calls(text, "head_window_attention")
        assert len(calls) == cfg.n_window + cfg.n_attention == 5
        labels = sorted(afmoe_trace.label_of(line, marks) for line in calls)
        assert labels == ["mixer_full_attention"] \
            + ["mixer_swa_attention"] * 4
        assert not _kernel_calls(text)
        return
    calls = _kernel_calls(text)
    assert len(calls) == 5
    labels = sorted(afmoe_trace.label_of(line, marks) for line in calls)
    assert labels == ["full_decode_attention"] + ["swa_decode_attention"] * 4


# ---------------------------------------------------------------------------
# the lfm2_moe layout: short-convolution layers whose state is kept at
# every block's end beside the K/V pools (ISSUE 52)


@pytest.fixture(scope="module")
def lfm2_cell(one_chip):
    """The shapes of ``serve-lfm2-agent4k-r80``, from the cell's own
    configuration file: 12 layers (9 conv : 3 attention) at published
    widths, all 32 experts, the whole vocabulary, 48 rows, a table of 72
    blocks of 64 a row, a snapshot a block."""
    import json
    import os
    from chipbench.traffic.open_loop_http_lfm2 import model_config
    from ray_tpu.inference.cache import snapshot_geometry
    from ray_tpu.models import hybrid
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "chipbench", "configs",
                           "lfm2-8b-a1b-12L.json")) as f:
        config = json.load(f)
    engine = config["engine"]
    cfg, _, _ = model_config(config)
    assert (cfg.n_short_conv, cfg.n_attention) == (9, 3)
    assert snapshot_geometry(cfg) == (9, 4096)
    on_chip = _on(one_chip)
    bs, rows = engine["kv_block_size"], engine["max_slots"]
    lay = PoolLayout(3, engine["n_blocks"] + 1, bs, *cfg.kv_geometry[1:])
    assert lay.shape == (3 * (engine["n_blocks"] + 1), 64, 512)
    params = jax.tree.map(
        lambda s: on_chip(s.shape, s.dtype),
        jax.eval_shape(lambda: hybrid.init_params(cfg,
                                                  jax.random.PRNGKey(0))))
    n_bytes = sum(int(np.prod(s.shape)) * s.dtype.itemsize
                  for s in jax.tree.leaves(params))
    assert 7.85e9 < n_bytes < 7.87e9            # 3,929 M bf16 parameters
    pools = (on_chip(lay.shape, cfg.dtype),) * 2
    state = (on_chip((9, rows, 2, 2048), cfg.dtype),
             on_chip((engine["n_blocks"] + 1, 9 * 4096), cfg.dtype))
    done = {}

    def compiled(which):
        from ray_tpu.inference import recurrent
        if which not in done:
            T = -(-engine["max_seq"] // bs)
            C = engine["prefill_chunk"]
            make, n = {
                "step": (recurrent.make_recurrent_decode_step,
                         (rows, T + 3)),
                "chunk": (recurrent.make_recurrent_chunk_fn, (T + C + 3,)),
                "step_chunk": (recurrent.make_recurrent_step_chunk,
                               (rows * (T + 3) + T + C + 3,))}[which]
            kw = {} if which == "step" else {"chunk": C}
            fn = make(cfg, block_size=bs, n_table=T, **kw)
            done[which] = fn.lower(params, pools, state,
                                   on_chip((rows,), jnp.int32),
                                   on_chip(n, jnp.int32)).compile()
        return done[which]
    return cfg, lay, state, engine, compiled


@pytest.mark.parametrize("which", ["step", "chunk", "step_chunk"])
def test_lfm2_programs_fit_and_move_no_pool_and_no_snapshot(lfm2_cell,
                                                            which):
    """All three programs compile for the described chip at the
    published widths and the cell's sizes, fit, and copy neither the K/V
    pools nor the snapshots (8,193 x 36,864 bf16 = 604 MB, written by
    ONE scatter of the blocks a pass closes): they enter and leave in
    place.  Each attention layer is one kernel call on the pool (the
    one-token programs), each experts layer two grouped matmuls, and
    the trace's table tells them apart."""
    from chipbench import lfm2_trace
    from ray_tpu.inference.recurrent import has_step_chunk
    cfg, lay, state, engine, compiled = lfm2_cell
    assert has_step_chunk(cfg)
    program = compiled(which)
    _assert_pool_stays_put(program, lay)
    _assert_token_array_donated(program, engine["max_slots"])
    text = program.as_text()
    snap = ",".join(map(str, state[1].shape))
    assert not re.findall(rf"= \(?bf16\[{snap}\]\S* copy(?:-start)?\(", text)
    mem = program.memory_analysis()
    # pools and snapshots are aliased to the program's results
    assert mem.alias_size_in_bytes >= 2 * int(np.prod(lay.shape)) * 2 \
        + int(np.prod(state[1].shape)) * 2
    marks = lfm2_trace.marks_of(engine, cfg)
    gmm = [line for line in text.splitlines()
           if " custom-call(" in line and "tpu_custom_call" in line
           and line.strip().startswith("%gmm")]
    assert len(gmm) == 2 * 10
    assert {lfm2_trace.label_of(line, marks) for line in gmm} \
        == {"routed_experts"}
    calls = _kernel_calls(text)
    assert len(calls) == (0 if which == "chunk" else 3)
    assert {lfm2_trace.label_of(line, marks) for line in calls} \
        <= {"decode_attention"}
    # a chunk's queries walk the row's table (72 blocks of 64: 4.5 key
    # blocks) head by head, in the lone program and in the fused one: a
    # kernel call an attention layer, the re-laid key block and the
    # values a K/V head each, and NO float32 array [w, h, span] (604 MB
    # a layer until PR 53) nor any other of the table's span beside the
    # heads.  The trace's table leaves the kernel in ``other``, not in a
    # label a roofline share reads.
    walk = _kernel_calls(text, "head_window_attention")
    assert len(walk) == (0 if which == "step" else 3)
    for line in walk:
        assert lfm2_trace.label_of(line, marks) == "other"
        assert "bf16[8,1024,64]" in line and "bf16[8,64,1024]" in line
    _no_table_span_by_heads(text, cfg, lay, engine)
    assert "f32[1024,32,4608]" not in text and "[1,4608,512]" not in text


def test_lfm2_step_chunk_multiplies_by_in_proj_once_a_layer(lfm2_cell):
    """The rows' tokens and the chunk's are ONE window: a convolution
    layer's ``in_proj`` product is ``rows + chunk`` rows wide and
    evaluated once (its barrier), whatever its consumers in the two
    parts."""
    cfg, lay, state, engine, compiled = lfm2_cell
    text = compiled("step_chunk").as_text()
    w = engine["max_slots"] + engine["prefill_chunk"]
    products = re.findall(
        rf"^\s*%\S+ = bf16\[(?:1,)?{w},6144\]\S* (?:dot|convolution)\(.*"
        rf"short_conv_in_proj", text, re.M)
    assert len(products) == cfg.n_short_conv, len(products)
    for part in (engine["max_slots"], engine["prefill_chunk"]):
        assert not re.findall(
            rf"bf16\[(?:1,)?{part},6144\]\S* (?:dot|convolution)\(", text)


# the xing4_0 layout: four residual streams round every sublayer of the
# latent layout, 64 held experts top-4 by sigmoid + bias, one shared


@pytest.fixture(scope="module")
def xing4_cell(one_chip):
    """The shapes of ``serve-xing4-code4k``, from the cell's own
    configuration file through its traffic kind's ``model_config``: 6
    layers (the first dense) at published widths, four streams of 3,584
    lanes, all 64 experts and the whole vocabulary held, 32 rows, 24,576
    + 1 blocks of 16 x 640 lanes, 1,040-block tables."""
    import json
    import os
    from chipbench.traffic.open_loop_http_xing4 import model_config
    from ray_tpu.models import hybrid
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "chipbench", "configs",
                           "xing4.0-29b-a4b-6L.json")) as f:
        config = json.load(f)
    cfg, published, _ = model_config(config)
    engine = config["engine"]
    on_chip = _on(one_chip)
    lay = PoolLayout(*cfg.kv_geometry[:1], engine["n_blocks"] + 1,
                     engine["kv_block_size"], *cfg.kv_geometry[1:], 1,
                     cfg.value_lanes)
    assert lay.shape == (6 * 24577, 16, 640)
    params = jax.tree.map(
        lambda s: on_chip(s.shape, s.dtype),
        jax.eval_shape(lambda: hybrid.init_params(cfg,
                                                  jax.random.PRNGKey(0))))
    return (cfg, on_chip, params, on_chip(lay.shape, cfg.dtype), lay,
            engine), published


def _materialised(text):
    """The result shapes of every instruction outside a fused
    computation: the arrays a program writes to memory."""
    out, fused = [], False
    for line in text.splitlines():
        if line.startswith(("%", "ENTRY")) and line.rstrip().endswith("{"):
            fused = line.startswith("%fused_computation")
        elif not fused and " = " in line:
            out.append(line.split(" = ", 1)[1].split(" ", 1)[0])
    return out


@pytest.mark.parametrize("which", ["step", "chunk"])
def test_xing4_programs_fit_move_no_pool_and_carry_the_streams_flat(
        xing4_cell, which):
    """Both programs of the cell, compiled for the described chip: they
    fit beside the weights and the pool, the pool is no program's copy,
    no expert stack is re-laid out; the one-token kernel takes 32 heads
    on the pool as stored; the four streams ride between the sublayers
    as ONE array ``[tokens, 14336]`` (no ``[tokens, 4, 4, 3584]``
    product of the residual map, no float32 copy of the streams); the
    maps' product with ``Phi`` is float32 on both sides; and the trace's
    table finds all four parts of the mix in the program's own ops."""
    cell, published = xing4_cell
    cfg, _, params, _, lay, engine = cell
    compiled = _latent_program(cell, which)
    _assert_pool_stays_put(compiled, lay, n_pools=1)
    text = compiled.as_text()
    _no_table_span_by_heads(text, cfg, lay, engine)
    _no_expert_stack_is_copied(text, params)
    tokens = engine["max_slots"] if which == "step" \
        else engine["prefill_chunk"]
    n, d = cfg.hc_mult, cfg.d_model
    # what the program MATERIALISES: the results of the instructions
    # outside its fused computations
    results = " ".join(_materialised(text))
    assert f"bf16[{tokens},{n * d}]" in results
    # ([tokens, 4, 3584] is also the routed experts' [tokens, top-4, d])
    for bad in (f"[{tokens},{n},{n},{d}]", f"f32[{tokens},{n * d}]"):
        assert bad not in results, bad
    # Phi enters a float32 product (never a bfloat16 copy of it)
    assert f"f32[{2 * n + n * n},{n * d}]" in text
    assert f"bf16[{2 * n + n * n},{n * d}]" not in text
    calls = _kernel_calls(text, "latent_decode_attention")
    if which == "chunk":
        assert not calls
        assert len(_kernel_calls(text, "latent_window_attention")) \
            == cfg.n_latent == 6
    else:
        assert len(calls) == 6
        pool = "bf16[" + ",".join(map(str, lay.shape)) + "]"
        for line in calls:
            assert pool in line.split(" custom-call(", 1)[1]
    from chipbench import xing4_trace
    marks = xing4_trace.marks_of(published, engine["max_slots"],
                                 engine["prefill_chunk"])
    # the text a trace's event has: the operands with their shapes
    from jax._src.lib import xla_client
    how = xla_client._xla.HloPrintOptions()
    how.print_operand_shape, how.print_metadata = True, False
    how.print_backend_config = False
    verbose = compiled.runtime_executable().hlo_modules()[0].to_string(how)
    labels, fused = {}, False
    for line in verbose.splitlines():
        if line.startswith(("%", "ENTRY")) and line.rstrip().endswith("{"):
            fused = line.startswith("%fused_computation")
        elif not fused and re.search(r" (fusion|convolution|custom-call)\(",
                                     line):
            label = xing4_trace.label_of(line.strip(), marks)
            labels[label] = labels.get(label, 0) + 1
    assert set(xing4_trace.MHC) <= set(labels), labels
    for label in ("routed_experts", "shared_expert", "dense_mlp",
                  "mixer_latent_proj"):
        assert labels.get(label), (label, labels)
