"""Every Pallas kernel the tree ships, and the multi-device train step, must
lower and compile for the TPU — checked from the CPU sandbox, no chip needed.

CPU tests run the kernels through the Pallas interpreter (or not at all:
off the TPU ``pallas_kernels.mode()`` is ``"off"``), where a kernel is
ordinary HLO and nothing the TPU toolchain refuses can show. Two checks
close that gap without a chip:

* ``lower(lowering_platforms=("tpu",))`` runs the real Pallas→Mosaic
  lowering and the partitioner's checks. It found a width-changing bitcast
  inside the pack kernels and a Pallas call under a multi-device jit.
* ``.compile()`` against a v5e 2x2 *topology description* (libtpu builds a
  compile-only client; skipped where it cannot) runs Mosaic and the XLA TPU
  compiler. It found an 8-bit vector shift Mosaic cannot legalize and an
  internal compiler check that input fusion of a degenerate relayout trips.

Neither says the numbers are right — ``chip_smoke.py`` Phase C runs the
same list of cases (``chip_smoke.kernel_cases``) on the chip against their
references.
"""

import functools
import re

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import chip_smoke

CASES = chip_smoke.kernel_cases()


@pytest.fixture(autouse=True)
def _kernels_on(monkeypatch):
    monkeypatch.setenv("HVD_PALLAS", "on")


@pytest.fixture(scope="module")
def v5e_devices():
    from jax.experimental import topologies

    try:
        return list(topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices)
    except Exception as exc:  # noqa: BLE001 - any libtpu failure: skip
        pytest.skip(f"no TPU compile-only client here: {exc}")


def lower_tpu(fn, *args):
    """Lower ``fn`` for the TPU. x64 is switched off for the trace: the
    suite enables it, production does not, and Mosaic has no 64-bit types."""
    with jax.enable_x64(False):
        return jax.jit(fn).trace(*args).lower(lowering_platforms=("tpu",))


def on_mesh(args, mesh):
    """Abstract operands re-placed on ``mesh`` (unsharded ones replicated)."""
    return [jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=NamedSharding(
            mesh, a.sharding.spec if a.sharding is not None else P()))
        for a in args]


def _cpu_mesh4():
    return Mesh(np.array(jax.devices()[:4]), ("hvd",))


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_lowers_for_tpu(name):
    case = CASES[name]
    assert lower_tpu(case.fn, *case.args).as_text().count(
        "tpu_custom_call") == case.calls


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(name, v5e_devices):
    case = CASES[name]
    mesh = Mesh(np.array(v5e_devices[:1]), ("hvd",))
    lower_tpu(case.fn, *on_mesh(case.args, mesh)).compile()


# positions x head width of one bf16 causal call -> what
# ``pallas_kernels.flash_route`` says of it and the Pallas calls of forward
# + backward: the shapes on the far side of each boundary, where only the
# TPU compiler can say that the chosen kernel fits.
_ROUTE_EDGES = {
    # the largest dq scratch within Mosaic's default VMEM limit, and the
    # first whose fused backward names a limit of its own; their K and V
    # (4 and 8 MiB in VMEM, over the 1 MiB an operand that was the cap until
    # PR 44) stay resident forward, as lagunas-train-s8192's (8192, 128) do
    (16384, 64): ("once", "fused", 2),
    (32768, 64): ("once", "fused", 2),
    (8192, 128): ("once", "fused", 2),
    # nemotron3s-train-s4096's
    (4096, 128): ("once", "fused", 2),
    # qwen3next-train-s16384's: 32 MiB of K + V resident, a 16 MiB scratch
    # that asks 37
    (16384, 256): ("once", "fused", 2),
    # both sides of ``_KV_VMEM_CAP``: 64 MiB of K + V in VMEM's lanes, both
    # pipeline buffers, is the last single-shot forward at either width, and
    # 128 MiB streams
    (65536, 64): ("once", "fused", 2),
    # both sides of ``_DQ_SCRATCH_CAP``: a 32 MiB scratch asks 48 MiB, and
    # a 64 MiB one takes the streaming pair; at d=64 the last is 32 MiB too,
    # 64 in VMEM's lanes, and asks 80
    (65536, 128): ("once", "fused", 2),
    (131072, 128): ("step_streaming", "streaming", 3),
    (131072, 64): ("step_streaming", "fused", 2),
}


def _flash_grads(window=None):
    """All three gradients of one causal flash call, forward inside."""
    from horovod_tpu.ops import pallas_kernels as pk

    return jax.grad(lambda q, k, v: jnp.sum(pk.flash_attention(
        q, k, v, causal=True, window=window).astype(jnp.float32)),
        argnums=(0, 1, 2))


@pytest.mark.parametrize("shape", sorted(_ROUTE_EDGES))
def test_flash_route_edges_compile_for_v5e(shape, v5e_devices):
    from horovod_tpu.ops import pallas_kernels as pk

    (t, d), (forward, backward, calls) = shape, _ROUTE_EDGES[shape]
    route = pk.flash_route(t, t, d, 2)
    assert (route["forward"], route["backward"]) == (forward, backward)

    mesh = Mesh(np.array(v5e_devices[:1]), ("hvd",))
    x = jax.ShapeDtypeStruct((1, t, 2, d), jnp.bfloat16)
    lowered = lower_tpu(_flash_grads(), *on_mesh([x, x, x], mesh))
    assert lowered.as_text().count("tpu_custom_call") == calls
    lowered.compile()


# positions of one bf16 causal call with keys 192 and values 128 wide
# (latent attention: kanana2-train-s16384's is 16,384, whose 12 MiB dq
# scratch, 16 in VMEM's 256 lanes, is the first to name its own limit) ->
# the route and the Pallas calls of forward + backward: a key width of a lane
# tile and a half, and K, V, out and dq each at its own width, through
# Mosaic; 32,768 and 65,536 are the two sides of ``_DQ_SCRATCH_CAP`` and of
# ``_KV_VMEM_CAP`` (48 and 96 MiB of K + V in VMEM)
_TWO_WIDTH_EDGES = {
    2048: ("once", "fused", 2),
    4096: ("once", "fused", 2),
    16384: ("once", "fused", 2),
    32768: ("once", "fused", 2),
    65536: ("step_streaming", "streaming", 3),
}


@pytest.mark.parametrize("t", sorted(_TWO_WIDTH_EDGES))
def test_two_width_flash_routes_compile_for_v5e(t, v5e_devices):
    from horovod_tpu.ops import pallas_kernels as pk

    forward, backward, calls = _TWO_WIDTH_EDGES[t]
    route = pk.flash_route(t, t, 192, 2, dv=128)
    assert (route["forward"], route["backward"]) == (forward, backward)

    mesh = Mesh(np.array(v5e_devices[:1]), ("hvd",))
    qk = jax.ShapeDtypeStruct((1, t, 2, 192), jnp.bfloat16)
    v = jax.ShapeDtypeStruct((1, t, 2, 128), jnp.bfloat16)
    lowered = lower_tpu(_flash_grads(), *on_mesh([qk, qk, v], mesh))
    tensors = dict(flash_call_tensors(lowered.as_text()))
    assert len(tensors) == calls
    # every operand and result at its own width: nothing padded to the other
    widths = {name: sorted({dims[-1] for dims, dtype in shapes
                            if len(dims) == 3 and dims[1] == t})
              for name, shapes in tensors.items()}
    assert all(w == [128, 192] for w in widths.values()), widths
    lowered.compile()


@pytest.mark.parametrize("layer", [
    "kanana2-train-s16384", "lagunas-train-s8192_full",
    "lagunas-train-s8192_window", "qwen3next-train-s16384"])
def test_long_cells_layers_compile_resident_inside_the_gradient(layer,
                                                                v5e_devices):
    """Forward + all three gradients of one layer's flash call at the
    cell's own head count (the three cells whose K and V are over 1 MiB an
    operand), inside ``jax.grad`` (Mosaic counts a few MiB more there than
    for the kernel alone, PERF.md §6, PR 43): the single-shot forward with
    a head's K and V in VMEM and the one-pass backward, compiled for a v5e
    (the limits the two calls name: ``tests/test_kanana.py``)."""
    from horovod_tpu.ops import pallas_kernels as pk
    from tests.test_kanana import _CELL_BACKWARD_VMEM

    (b, t, h, d, dv, window), _ = _CELL_BACKWARD_VMEM[layer]
    assert 4 * 2 ** 20 < pk._kv_vmem(t, d, dv, 2) <= pk._KV_VMEM_CAP
    mesh = Mesh(np.array(v5e_devices[:1]), ("hvd",))
    qk = jax.ShapeDtypeStruct((b, t, h, d), jnp.bfloat16)
    v = jax.ShapeDtypeStruct((b, t, h, dv), jnp.bfloat16)
    lowered = lower_tpu(_flash_grads(window), *on_mesh([qk, qk, v], mesh))
    assert sorted(re.findall(r'kernel_name = "(flash_\w+)"',
                             lowered.as_text())) == ["flash_bwd", "flash_fwd"]
    lowered.compile()


# name -> (batch, heads, positions): the attention calls of the four cells
# (gpt2-medium's is both its cells'), and one ring hop at chip_smoke's shape
_STATISTICS_CASES = {
    "gpt2m_8x16x1024": (8, 16, 1024),
    "gpt2l_4x20x1024": (4, 20, 1024),
    "granite4hm_1x32x4096": (1, 32, 4096),
    "ring_hop_1x16x2048": (1, 16, 2048),
}
_FLASH_CALL = re.compile(
    r'custom_call @tpu_custom_call\(.*kernel_name = "(flash_\w+)".*'
    r' : \((.*)\) -> \(?(.*?)\)?$')
_TENSOR = re.compile(r"tensor<([0-9x]+)x(\w+)>")


def flash_call_tensors(lowered_text):
    """``[(kernel name, [(dims, dtype) of every operand and result])]`` of
    the flash kernels' custom calls in a program lowered for the TPU."""
    calls = []
    for line in lowered_text.splitlines():
        m = _FLASH_CALL.search(line)
        if m:
            calls.append((m.group(1), [
                (tuple(int(n) for n in dims.split("x")), dtype)
                for dims, dtype in _TENSOR.findall(m.group(2) + m.group(3))]))
    return calls


@pytest.mark.parametrize("name", sorted(_STATISTICS_CASES))
def test_flash_statistics_cross_hbm_lane_dense(name):
    """No flash kernel takes or gives an f32 tensor whose last dimension is
    1 (the TPU tiles it to 128 lanes: 512 bytes a value in HBM and in every
    DMA): lse and a ring hop's m and l are [BH, 1, T] rows, the residual
    ``flash_attention`` saves for its backward pass is that row as the
    forward kernel wrote it, and the row sums of do * out are no operand
    at all (the backward kernels form them)."""
    from horovod_tpu.ops import pallas_kernels as pk

    b, h, t = _STATISTICS_CASES[name]
    x = jax.ShapeDtypeStruct((b, t, h, 64), jnp.bfloat16)
    row = (b * h, 1, t)
    if name.startswith("ring_hop"):
        stat = jax.ShapeDtypeStruct((b, h, t), jnp.float32)
        acc = jax.ShapeDtypeStruct(x.shape, jnp.float32)

        def fn(q, k, v, m, l, o, q_off, k_off):
            m, l, o = pk.flash_attention_step(
                q, k, v, m, l, o, q_off, k_off, causal=True, scale=0.125)
            out, lse = pk.finalize_attention_stats(m, l, o, q.dtype)
            return pk._flash_bwd(q, k, v, out, lse, o, q_off, k_off,
                                 causal=True, scale=0.125)

        off = jax.ShapeDtypeStruct((), jnp.int32)
        args = (x, x, x, stat, stat, acc, off, off)
        want = {"flash_step": 4, "flash_bwd": 1}      # m, l in and out; lse
    else:
        def attn(q, k, v):
            return pk.flash_attention(q, k, v, causal=True)

        def fn(q, k, v):
            out, vjp = jax.vjp(attn, q, k, v)
            return vjp(out)

        args = (x, x, x)
        want = {"flash_fwd": 1, "flash_bwd": 1}
        # what jax.vjp's backward function closes over: q, k, v and out
        # heads-major in bf16, and the one f32 row
        with jax.enable_x64(False):
            saved = jax.tree_util.tree_leaves(jax.eval_shape(
                lambda *qkv: jax.vjp(attn, *qkv)[1], *args))
        assert sorted((a.shape, a.dtype.name) for a in saved) == sorted(
            [((b * h, t, 64), "bfloat16")] * 4 + [(row, "float32")])
    calls = flash_call_tensors(lower_tpu(fn, *args).as_text())
    assert sorted(n for n, _ in calls) == sorted(want)
    for kernel, tensors in calls:
        f32 = [dims for dims, dtype in tensors if dtype == "f32"]
        assert not [dims for dims in f32 if dims[-1] == 1], (kernel, f32)
        assert f32.count(row) == want[kernel], (kernel, f32)


def _four_chip_cases(mesh):
    fn, _, args = chip_smoke.matmul_reduce_scatter_case(mesh)
    # one chunk matmul per ring position
    yield fn, args, 4
    for wire in ("int8", "int4"):
        fn, _, arg = chip_smoke.quantized_allreduce_case(mesh, wire)
        # 3 reduce-scatter hops + the owner's one pack for the all-gather
        yield fn, [arg], 4
    fn, _, args = chip_smoke.ring_attention_case(mesh)
    # forward step, and dq/dkv through the fused backward, per ring pass
    yield fn, args, None


def test_four_chip_cases_lower_for_tpu():
    for fn, args, calls in _four_chip_cases(_cpu_mesh4()):
        found = lower_tpu(fn, *args).as_text().count("tpu_custom_call")
        assert found == calls if calls is not None else found > 0


def test_four_chip_cases_compile_for_v5e(v5e_devices):
    mesh = Mesh(np.array(v5e_devices[:4]), ("hvd",))
    for fn, args, _ in _four_chip_cases(mesh):
        lower_tpu(fn, *args).compile()


LAYERS = 2


def _train_step_lowering(mesh, per_chip_batch, remat="none", seq=256,
                         layers=LAYERS):
    """The data-parallel LM step over ``mesh`` with the model's default
    (Pallas) attention, lowered for the TPU."""
    import optax

    from horovod_tpu import spmd
    from horovod_tpu.models.transformer import TransformerLM, lm_loss

    model = TransformerLM(vocab_size=1024, num_layers=layers, num_heads=4,
                          d_model=256, max_seq_len=seq, remat=remat)

    def loss_fn(p, batch):
        x, y = batch
        return lm_loss(model.apply({"params": p}, x), y)

    tx = optax.adamw(3e-4)
    repl = NamedSharding(mesh, P())

    def abstract(tree):
        return jax.tree_util.tree_map(lambda l: jax.ShapeDtypeStruct(
            l.shape, l.dtype, sharding=repl), tree)

    params = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, seq), jnp.int32))["params"])
    tok = jax.ShapeDtypeStruct(
        (per_chip_batch * mesh.devices.size, seq), jnp.int32,
        sharding=NamedSharding(mesh, P("hvd")))
    step = spmd.make_train_step(loss_fn, tx, mesh=mesh)
    with jax.enable_x64(False):
        return step.trace(
            abstract(params), abstract(jax.eval_shape(tx.init, params)),
            (tok, tok)).lower(lowering_platforms=("tpu",))


def test_multi_device_train_step_lowers_with_flash_kernel():
    """Under a plain multi-device jit this raised 'Mosaic kernels cannot be
    automatically partitioned'."""
    text = _train_step_lowering(_cpu_mesh4(), 2).as_text()
    # one Mosaic body a kernel, forward and fused backward, whatever the
    # depth (the dispatchers are jitted), and a call of each a layer
    assert text.count("tpu_custom_call") == 2
    for dispatcher in ("_flash_fwd_once_call", "_flash_bwd_fused"):
        assert len(re.findall(rf"call @{dispatcher}\(", text)) == LAYERS


@pytest.mark.parametrize("per_chip_batch", [2, 1])
def test_multi_device_train_step_compiles_for_v5e(per_chip_batch,
                                                  v5e_devices):
    """One sequence a chip is the degenerate relayout input fusion must
    stay away from (``pallas_kernels._relayout_fusable``)."""
    mesh = Mesh(np.array(v5e_devices[:4]), ("hvd",))
    compiled = _train_step_lowering(mesh, per_chip_batch).compile()
    assert "all-gather" not in compiled.as_text()


@pytest.mark.parametrize("remat", ["none", "full", "dots"])
def test_recomputation_does_not_rerun_the_flash_forward(remat, v5e_devices):
    """Every recomputation policy keeps the forward kernel's two outputs
    (``models.transformer.REMAT_POLICIES``), so the compiled step runs the
    kernel once a layer in the forward pass and never again in the backward
    pass: with a policy that keeps nothing it was ``3 * LAYERS`` calls."""
    mesh = Mesh(np.array(v5e_devices[:1]), ("hvd",))
    compiled = _train_step_lowering(mesh, 2, remat=remat).compile()
    assert compiled.as_text().count(
        'custom_call_target="tpu_custom_call"') == 2 * LAYERS


def _kernel_calls(hlo_text, kernels):
    """``(kernel, instruction name, op_name path)`` of every Pallas call of
    a compiled program whose kernel's name matches the regex ``kernels``."""
    return [(kernel, kernel + suffix, path) for kernel, suffix, path in
            re.findall(rf"%({kernels})([.\d]*) = [^\n]*custom_call_target="
                       r'"tpu_custom_call"[^\n]*op_name="([^"]*)"', hlo_text)]


def _kernel_body_traces(spans, program):
    """The ``compile/trace`` spans of ``pallas_call``'s inner function
    (``wrapped``: one trace of a kernel's body each) nested in the trace of
    ``program``; a span's ``parent`` is the listed span around it."""
    by_id = {s.id: s for s in spans}

    def under(s):
        while s.parent is not None:
            s = by_id[s.parent]
            if s.name == "compile/trace" and s.program == program:
                return True
        return False

    return [s for s in spans if s.name == "compile/trace"
            and s.program == "wrapped" and under(s)]


@pytest.mark.parametrize("chips", [1, 4])
@pytest.mark.parametrize("remat", ["none", "full"])
def test_flash_kernels_are_traced_and_lowered_once_a_shape(remat, chips,
                                                           v5e_devices):
    """The flash dispatchers are under ``jax.jit`` (the rule below
    ``pallas_kernels._named_call``): a four-layer step traces each kernel's
    body once, forward and backward, where it traced one a call site
    (eight), and a fifth layer adds none; the lowered module holds one
    Mosaic body a kernel (a recomputed block on one chip traces the forward
    a second time and lowers it once: ``jax.checkpoint`` traces the block
    with no abstract mesh set, the backward pass replays it under the empty
    one, and jit's cache keys on the difference; ``shard_map`` sets its own
    for both); and in the compiled step every site is still a
    ``tpu_custom_call`` named for its kernel, under its own ``block_<i>``
    and, in the backward pass, ``transpose(``: what the benchmark's op
    class ``attention_kernel`` and scope classes ``attn_fwd`` /
    ``attn_bwd`` read. On four chips the calls sit inside ``shard_map``."""
    from chipbench import op_scopes, trace_reduce
    from horovod_tpu.metrics import phases
    from horovod_tpu.ops import pallas_kernels as pk

    op_classes = trace_reduce.load_classes()
    scope_classes = trace_reduce.load_classes(op_scopes.SCOPE_CLASSES)
    phases.install_jax_listeners()
    mesh = Mesh(np.array(v5e_devices[:chips]), ("hvd",))
    for layers in (4, 5):
        for dispatcher in (pk._flash_fwd_once_call, pk._flash_bwd_fused):
            dispatcher.clear_cache()    # an earlier test's trace of the shape
        recorder = phases.reset()
        lowered = _train_step_lowering(mesh, 2, remat=remat, layers=layers)
        bodies = _kernel_body_traces(recorder.spans(), "step")
        assert len(bodies) == 2 + (remat == "full" and chips == 1), (
            layers, len(bodies))
        text = lowered.as_text()
        assert sorted(re.findall(r'kernel_name = "(\w+)"', text)) == [
            "flash_bwd", "flash_fwd"]
        assert text.count("tpu_custom_call") == 2
        if layers == 5:
            continue
        calls = _kernel_calls(lowered.compile().as_text(), r"flash_\w+?")
        assert sorted(name for name, _, _ in calls) == \
            ["flash_bwd"] * layers + ["flash_fwd"] * layers
        for name, instruction, path in calls:
            dispatcher, scope = {
                "flash_fwd": ("_flash_fwd_once_call", "attn_fwd"),
                "flash_bwd": ("_flash_bwd_fused", "attn_bwd")}[name]
            assert re.search(rf"/block_\d/jit\({dispatcher}\)/{name}/", path)
            assert ("transpose(" in path) == (name == "flash_bwd"), path
            assert ("shard_map" in path) == (chips == 4), path
            assert trace_reduce.classify(path, scope_classes) == scope
            assert trace_reduce.classify(
                f"tpu_custom_call %{instruction}", op_classes) \
                == "attention_kernel"
        for name in ("flash_fwd", "flash_bwd"):
            assert sorted(re.search(r"/block_(\d)/", path).group(1)
                          for kernel, _, path in calls if kernel == name) \
                == [str(i) for i in range(layers)]


@pytest.mark.parametrize("remat", ["none", "full"])
def test_train_step_at_1024_positions_compiles_for_v5e(remat, v5e_devices):
    """At 1024 positions the causal backward cuts its 512 x 1024 cells into
    strips of sub-tiles and the forward runs its one key block without a
    loop, both with the step's heads-major relayouts fused into their
    reads; the steps above stop at 256 positions, one whole tile. Still one
    forward and one backward call a layer."""
    from horovod_tpu.ops import pallas_kernels as pk

    assert pk._pick_sub_tile(True, 512, 1024) != (512, 1024)
    mesh = Mesh(np.array(v5e_devices[:1]), ("hvd",))
    compiled = _train_step_lowering(mesh, 2, remat=remat, seq=1024).compile()
    assert compiled.as_text().count(
        'custom_call_target="tpu_custom_call"') == 2 * LAYERS


def _tgmm_scopes(hlo_text):
    """The innermost of the routed layer's scopes around every ``moe_tgmm``
    call of a compiled program, sorted: ``experts`` for a weight-gradient
    product, ``combine`` / ``dispatch`` for ``put_rows``' segment sum
    (``moe_experts_ms`` reads the scope ``experts``, and ``put_rows`` must
    stay outside it)."""
    return sorted(re.search(
        r"[/(]moe[/)].*/(experts|combine|dispatch)/jit\(_tgmm\)/moe_tgmm/",
        path).group(1) for _, _, path in _kernel_calls(hlo_text, "moe_tgmm"))


def _put_rows_takes_the_kernel(rows, d):
    """``put_rows`` of ``[rows, d]`` bfloat16 rows goes to ``tgmm``: what
    ``grouped_outer`` asks of ``kernel_path`` for its one-hot and rows."""
    from horovod_tpu.ops import moe, pallas_kernels as pk

    return pk.kernel_path(
        "grouped_outer",
        jax.ShapeDtypeStruct((rows, moe._TOKEN_TILE), jnp.bfloat16),
        jax.ShapeDtypeStruct((rows, d), jnp.bfloat16)) == "pallas"


# family -> tokens, d, expert width, experts, held, top_k, the row
# capacities, and what the family's layer passes ``routed_ffn`` by keyword:
# LFM2-8B-A1B at its cell's 16,384 tokens, and Laguna-S-2.1's sparse layers
# (softmax scores, weights 2.5 x p / sum) at its cell's 8,192
_SWIGLU_LAYERS = {
    "lfm2_moe": (16384, 2048, 1792, 32, 8, 4, (32768, 65536), {}),
    "laguna": (8192, 3072, 1024, 256, 16, 10, (20480, 81920),
               {"scale": 2.5, "scoring": "softmax"}),
}


@pytest.mark.parametrize("family", sorted(_SWIGLU_LAYERS))
def test_routed_feed_forward_compiles_for_v5e_at_the_published_widths(
        family, v5e_devices):
    """``ops/moe.routed_ffn`` forward and backward at a family's widths and
    its cell's tokens: the grouped products are the Pallas kernels
    ``moe_gmm`` / ``moe_tgmm``, seven a row capacity (two forward; one
    recomputed, two against the transposed matrices and two weight-gradient
    products in the hand-written backward pass), none is XLA's ragged-dot
    kernel, and nothing of the layer is a dense product over experts x
    tokens. ``put_rows`` is two more ``moe_tgmm`` a capacity, the forward's
    under ``combine`` and ``d_h``'s under ``dispatch``, whose operand is the
    ``[rows, 128]`` one-hot of a row's place in its tile of tokens."""
    from jax.sharding import SingleDeviceSharding

    from horovod_tpu.ops import moe, pallas_kernels as pk

    tokens, d, width, experts, held, top_k, capacities, keywords = \
        _SWIGLU_LAYERS[family]
    one = SingleDeviceSharding(v5e_devices[0])

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one)

    def loss(h, router, bias, w_in, w_out):
        y = moe.routed_ffn(h, router, bias, w_in, w_out,
                           held=tuple(range(held)), top_k=top_k,
                           **keywords)[0]
        return jnp.sum(y.astype(jnp.float32) ** 2)

    sizes = moe.capacities(tokens * top_k, held, experts)
    assert sizes == capacities
    for rows in sizes:
        for k, n in ((d, 2 * width), (width, d), (2 * width, d)):
            assert pk.grouped_route(rows, k, n, 2)["path"] == "pallas"
    with jax.enable_x64(False):
        text = jax.jit(jax.grad(loss, argnums=(0, 1, 3, 4))).trace(
            shape((tokens, d), jnp.bfloat16), shape((d, experts), jnp.float32),
            shape((experts,), jnp.float32),
            shape((held, d, 2 * width), jnp.float32),
            shape((held, width, d), jnp.float32)).lower(
                lowering_platforms=("tpu",)).compile().as_text()
    assert "ragged-dot" not in text
    calls = re.findall(r"%(moe_t?gmm)[.\d]* = [^\n]*custom_call_target="
                       r'"tpu_custom_call"', text)
    assert calls.count("moe_gmm") == len(sizes) * (2 + 3), calls
    assert _tgmm_scopes(text) == sorted(
        len(sizes) * ["experts", "experts", "combine", "dispatch"])
    for rows in sizes:
        assert _put_rows_takes_the_kernel(rows, d)
        assert f"bf16[{rows},{moe._TOKEN_TILE}]" in text
        assert f"bf16[{rows},{2 * width}]" in text
    # no [experts, tokens, d] or [tokens, experts, d] operand anywhere
    assert not re.search(rf"\[({held}|{experts}),{tokens},{d}\]", text)
    assert not re.search(rf"\[{tokens},({held}|{experts}),{d}\]", text)


def test_latent_routed_feed_forward_compiles_for_v5e_at_the_published_widths(
        v5e_devices):
    """``ops/moe.routed_ffn`` forward and backward as
    NVIDIA-Nemotron-3-Super-120B-A12B's LatentMoE layer calls it, at the
    cell's 4,096 tokens: top-22 of 512 with 8 held, squared-ReLU experts
    2688 wide reading a latent of 1024 beside the router's 4096-wide input.
    The grouped products are the Pallas kernels at both row capacities, the
    same seven a capacity as the SwiGLU stage's with ``put_rows``' two
    beside them, and ``w_in`` is one expert width wide, not two."""
    from jax.sharding import SingleDeviceSharding

    from horovod_tpu.ops import moe

    tokens, d, latent, width, experts, held, top_k = \
        4096, 4096, 1024, 2688, 512, 8, 22
    one = SingleDeviceSharding(v5e_devices[0])

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one)

    def loss(h, x, router, bias, w_in, w_out):
        y = moe.routed_ffn(h, router, bias, w_in, w_out,
                           held=tuple(range(held)), top_k=top_k, x=x,
                           activation="relu2", scale=5.0, norm_eps=1e-20)[0]
        return jnp.sum(y.astype(jnp.float32) ** 2)

    sizes = moe.capacities(tokens * top_k, held, experts)
    assert sizes == (11264, 90112)
    with jax.enable_x64(False):
        text = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 4, 5))).trace(
            shape((tokens, d), jnp.bfloat16),
            shape((tokens, latent), jnp.bfloat16),
            shape((d, experts), jnp.float32), shape((experts,), jnp.float32),
            shape((held, latent, width), jnp.float32),
            shape((held, width, latent), jnp.float32)).lower(
                lowering_platforms=("tpu",)).compile().as_text()
    assert "ragged-dot" not in text
    calls = re.findall(r"%(moe_t?gmm)[.\d]* = [^\n]*custom_call_target="
                       r'"tpu_custom_call"', text)
    assert calls.count("moe_gmm") == len(sizes) * (2 + 3), calls
    assert _tgmm_scopes(text) == sorted(
        len(sizes) * ["experts", "experts", "combine", "dispatch"])
    for rows in sizes:
        assert _put_rows_takes_the_kernel(rows, latent)
        assert f"bf16[{rows},{width}]" in text
        assert f"bf16[{rows},{2 * width}]" not in text


@pytest.mark.parametrize("latent", [0, 128], ids=["plain", "latent"])
def test_a_recomputed_block_sums_rows_back_only_where_the_sum_is_kept(
        latent, v5e_devices):
    """A routed block of a model under ``remat="full"``, compiled for a
    v5e: ``put_rows`` is a ``moe_tgmm`` under ``combine`` in the forward
    pass and one under ``dispatch`` in the hand-written backward pass
    (``d_h``), a layer and capacity; the recomputed forward's is dead code
    (the stage's backward pass keeps the layer's operands, not its result)
    unless something after the experts keeps their sum for its own
    gradient, as a latent's up-projection does: three then."""
    from horovod_tpu.models.hybrid import HybridLM
    from horovod_tpu.ops import moe

    layers, seq, top_k, experts, held = 2, 256, 2, 8, (0, 1)
    model = HybridLM(
        vocab_size=512, layer_kinds=("attention",) * layers, d_model=128,
        ffn_width=256, attn_heads=2, attn_kv_heads=2, attn_head_dim=64,
        ssm_heads=8, ssm_head_dim=16, ssm_state=128, ssm_chunk=64,
        ffn_kinds=("moe",) * layers, moe_experts=experts, moe_held=held,
        moe_top_k=top_k, moe_width=128, moe_latent=latent, remat="full")
    sizes = moe.capacities(seq * top_k, len(held), experts)
    assert sizes == (256, 512)
    text = _model_grad_text(model, seq, v5e_devices)
    each = layers * len(sizes)
    assert _tgmm_scopes(text) == sorted(
        each * ["experts", "experts", "dispatch"]
        + each * (2 if latent else 1) * ["combine"])
    sums = [path for _, _, path in _kernel_calls(text, "moe_tgmm")
            if "/experts/" not in path]
    assert sum("rematted_computation" in path for path in sums) \
        == (each if latent else 0)
    assert sum("/dispatch/" in path and "transpose(" in path
               for path in sums) == each


@pytest.mark.parametrize("latent", [0, 128], ids=["plain", "latent"])
def test_the_capacity_that_ran_is_a_scope_in_every_pass(latent, v5e_devices):
    """The routed blocks of a model under ``remat="full"``, compiled for a
    v5e: every operation of the expert stage lies under ``capacity_fit``
    (the branch of the smaller size) or ``capacity_all`` (the worst case's),
    whatever the branch's index: in the forward pass, in the hand-written
    backward pass and, where something after the experts keeps their sum (a
    latent's up-projection), in the recomputed forward; the router and the
    assignments' order lie under neither. Read with the benchmark's own
    patterns: ``moe_worst_case_ms`` / ``moe_fit_ms`` / ``moe_router_ms``
    class the paths by those scopes, and each older ``moe_*`` reader
    matches a path exactly where it matched the parent's, the same path
    without the new element."""
    from chipbench.layer_metrics import (moe_dispatch_ms, moe_experts_ms,
                                         moe_fit_ms, moe_latent_ms, moe_ms,
                                         moe_router_ms, moe_shared_ms,
                                         moe_worst_case_ms)
    from horovod_tpu.models.hybrid import HybridLM
    from horovod_tpu.ops import moe

    layers, seq, top_k, experts, held = 2, 256, 2, 8, (0, 1)
    model = HybridLM(
        vocab_size=512, layer_kinds=("attention",) * layers, d_model=128,
        ffn_width=256, attn_heads=2, attn_kv_heads=2, attn_head_dim=64,
        ffn_kinds=("moe",) * layers, moe_experts=experts, moe_held=held,
        moe_top_k=top_k, moe_width=128, moe_latent=latent,
        moe_shared_width=128, remat="full")
    assert moe.capacities(seq * top_k, len(held), experts) == (256, 512)
    text = _model_grad_text(model, seq, v5e_devices)
    paths = set(re.findall(r'op_name="([^"]*)"', text))

    def passes(scope):
        """The passes in which some operation lies under ``scope``."""
        return {"recomputed" if "rematted_computation" in p else
                "backward" if "transpose(" in p else "forward"
                for p in paths if f"/{scope}/" in p}

    everywhere = {"forward", "backward", "recomputed"}
    for scope in ("capacity_fit", "capacity_all"):
        assert passes(scope) == (everywhere if latent
                                 else everywhere - {"recomputed"}), scope
    assert passes("moe/router") == everywhere
    stage = {p for p in paths if re.search(r"/moe/cond/branch_\d_fun", p)}
    assert stage and all(re.search(
        r"/moe/cond/branch_(0_fun/capacity_fit|1_fun/capacity_all)(/|$)", p)
        for p in stage)
    # the grouped products and ``put_rows``' sums: half under each
    calls = [path for _, _, path in _kernel_calls(text, "moe_t?gmm")]
    assert len(calls) == layers * 2 * (12 if latent else 9)
    assert sum("/capacity_fit/" in p for p in calls) \
        == sum("/capacity_all/" in p for p in calls) == len(calls) // 2

    older = (moe_ms, moe_experts_ms, moe_dispatch_ms, moe_shared_ms,
             moe_latent_ms)
    read = dict.fromkeys(older, 0)
    for path in paths:
        as_the_parent_wrote_it = re.sub(r"/capacity_(fit|all)(?=/|$)", "",
                                        path)
        for module in older:
            hit = bool(re.search(module.PATTERN, path))
            assert hit is bool(re.search(module.PATTERN,
                                         as_the_parent_wrote_it)), path
            read[module] += hit
        for module, scope in ((moe_worst_case_ms, "/capacity_all"),
                              (moe_fit_ms, "/capacity_fit"),
                              (moe_router_ms, "/moe/router")):
            assert bool(re.search(module.PATTERN, path)) \
                is bool(re.search(scope + "(/|$)", path)), path
    assert all(read[module] for module in older
               if latent or module is not moe_latent_ms), read


# name -> heads, groups (None: B and C [b, T, N]), chunk, head width,
# state, positions: the Mamba-2 layer of granite-4.0-h-micro and of
# NVIDIA-Nemotron-3-Super-120B-A12B at the cells' one sequence of 4096
# positions; and the gate's other corners at a padded length: a head a
# lane width (nothing to pick between heads) with a state of two, eight
# heads a lane width
_SCAN_CASES = {"granite": (64, None, 256, 64, 128, 4096),
               "nemotron_h": (128, 8, 128, 64, 128, 4096),
               "a_head_a_lane_width": (16, 2, 128, 128, 256, 700),
               "narrow_heads": (64, 8, 64, 16, 128, 100)}


@pytest.mark.parametrize("name", sorted(_SCAN_CASES))
def test_scan_kernels_compile_for_v5e_at_the_published_widths(name,
                                                               v5e_devices):
    """``ops/ssd.ssd_chunked`` forward and backward at the two cells'
    shapes: the scan is the Pallas pair ``ssd_fwd`` (the variant that saves
    the tiles' entering states) and ``ssd_bwd``, nothing of it is the dual
    form's ``[b, c, H, L, L]`` decay or ``[b, c, H, P, N]`` float32
    states, and what is saved is one ``[N, 128]`` state a tile and lane
    width of heads."""
    from jax.sharding import SingleDeviceSharding

    from horovod_tpu.ops import pallas_kernels as pk, ssd

    (h, groups, chunk, p, n, t), b = _SCAN_CASES[name], 1
    one = SingleDeviceSharding(v5e_devices[0])

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one)

    bc = (b, t, n) if groups is None else (b, t, groups, n)
    operands = (shape((b, t, h, p), jnp.bfloat16), shape((b, t, h), jnp.float32),
                shape((h,), jnp.float32), shape(bc, jnp.bfloat16),
                shape(bc, jnp.bfloat16), shape((h,), jnp.float32))
    assert pk.kernel_path("ssd_scan", operands[0], operands[3]) == "pallas"
    route = pk.ssd_route(t, h, p, n, groups or 1)

    def loss(*a):
        return jnp.sum(ssd.ssd_chunked(*a, chunk=chunk).astype(jnp.float32) ** 2)

    with jax.enable_x64(False):
        forward = jax.jit(functools.partial(ssd.ssd_chunked, chunk=chunk)).trace(
            *operands).lower(lowering_platforms=("tpu",)).compile().as_text()
        text = jax.jit(jax.grad(loss, argnums=range(6))).trace(
            *operands).lower(lowering_platforms=("tpu",)).compile().as_text()
    assert [name for name, _ in _scan_calls(forward)] == ["ssd_fwd"]
    assert sorted(name for name, _ in _scan_calls(text)) == \
        ["ssd_bwd", "ssd_fwd"]
    tiles, widths = -(-t // route["tile"]), h * p // 128
    assert f"f32[{b},{tiles},{widths},{n},128]" in text
    assert f"f32[{b},{tiles},{widths},{n},128]" not in forward
    for c, edge in ((-(-t // chunk), chunk), (tiles, route["tile"])):
        for dims in ((b, c, h, p, n), (b, c, h, edge, edge)):
            assert "[" + ",".join(map(str, dims)) + "]" not in text, dims


def _scan_calls(hlo_text):
    """``(kernel, op_name path)`` of every call of a scan kernel."""
    return [(kernel, path) for kernel, _, path in
            _kernel_calls(hlo_text, "ssd_fwd|ssd_bwd")]


def _model_grad_text(model, seq, v5e_devices):
    """The compiled gradient, for a v5e, of the sum of ``model``'s logits on
    one sequence of ``seq`` tokens."""
    from jax.sharding import SingleDeviceSharding

    one = SingleDeviceSharding(v5e_devices[0])
    toks = jax.ShapeDtypeStruct((1, seq), jnp.int32, sharding=one)
    params = jax.tree_util.tree_map(
        lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=one),
        jax.eval_shape(lambda: model.init(
            jax.random.PRNGKey(0), jnp.zeros((1, seq), jnp.int32))["params"]))
    with jax.enable_x64(False):
        return jax.jit(jax.grad(lambda p, x: jnp.sum(
            model.apply({"params": p}, x).astype(jnp.float32)))).trace(
                params, toks).lower(
                    lowering_platforms=("tpu",)).compile().as_text()


def _hybrid_grad_text(v5e_devices, remat):
    """The compiled gradient of a two-layer Mamba-2 model whose scan the
    kernels take (8 heads of 16, state 128, 256 positions), for a v5e."""
    from horovod_tpu.models.hybrid import HybridLM

    model = HybridLM(
        vocab_size=512, layer_kinds=("mamba", "mamba"), d_model=128,
        ffn_width=256, attn_heads=4, attn_kv_heads=2, attn_head_dim=32,
        ssm_heads=8, ssm_head_dim=16, ssm_state=128, ssm_chunk=64,
        remat=remat)
    return _model_grad_text(model, 256, v5e_devices)


@pytest.mark.parametrize("remat", ["none", "full"])
def test_hybrid_step_runs_the_scan_kernels_under_the_ssd_scope(remat,
                                                                v5e_devices):
    """A Mamba-2 layer's scan is ``ssd_fwd`` in the forward pass, again in
    a recomputed block (``remat="full"`` keeps a block's input, not the
    tiles' states), and ``ssd_bwd``; every one of them sits under
    ``block_<i>/mixer/ssd``, the backward kernel too (``ssd_ms`` reads the
    scope, and a backward kernel outside it would read as a gain)."""
    text = _hybrid_grad_text(v5e_devices, remat)
    calls = _scan_calls(text)
    layers = 2
    assert sorted(name for name, _ in calls) == \
        ["ssd_bwd"] * layers + ["ssd_fwd"] * layers * (2 if remat == "full"
                                                       else 1)
    for name, path in calls:
        block, = re.findall(
            r"block_(\d)/mixer/ssd/jit\(_" + name + r"\)/" + name, path)
        assert ("transpose(" in path) == (name == "ssd_bwd"
                                          or "rematted_computation" in path)
    for block in range(layers):
        paths = [path for _, path in calls if f"block_{block}/" in path]
        assert len(paths) == len(calls) // layers
    if remat == "full":
        assert sum("rematted_computation" in path for _, path in calls) \
            == layers
    # nothing of the dual form: no state of a chunk crosses HBM
    assert not re.search(r"f32\[1,\d+,8,16,128\]", text)


@pytest.mark.parametrize("remat", ["none", "full"])
def test_hybrid_step_runs_the_conv_kernels_under_the_conv_scope(remat,
                                                                 v5e_devices):
    """A Mamba-2 layer's conv is ``conv_fwd`` a part (``x``, ``B``, ``C``)
    in the forward pass, again in a recomputed block, and ``conv_bwd`` a
    part; every one sits under ``block_<i>/mixer/conv``, which
    ``causal_conv_ms`` reads, the backward kernel too. A call whose operand
    took XLA's producer (the slice of ``in_proj``'s output) is a ``fusion``
    of kind ``kCustom`` that carries the call's name and path. No float32
    copy of a part crosses HBM: not ``padded``, not the pre-activation."""
    from chipbench.layer_metrics import causal_conv_ms

    text = _hybrid_grad_text(v5e_devices, remat)
    entry = text[text.index("\nENTRY "):]
    calls = re.findall(
        r"%(conv_fwd|conv_bwd)[.\d]* = [^\n]*? (?:custom-call|fusion)\("
        r'[^\n]*op_name="([^"]*)"', entry)
    layers, parts = 2, 3
    assert sorted(name for name, _ in calls) == \
        ["conv_bwd"] * layers * parts \
        + ["conv_fwd"] * layers * parts * (2 if remat == "full" else 1)
    for name, path in calls:
        assert re.search(causal_conv_ms.PATTERN, path), path
        assert re.search(
            r"block_\d/mixer/conv/jit\(_" + name + r"\)/" + name, path)
        assert ("transpose(" in path) == (name == "conv_bwd"
                                          or "rematted_computation" in path)
    assert not re.search(r"f32\[1,25[69],(128|384)\]", entry)


@pytest.mark.parametrize("remat", ["none", "full"])
def test_window_and_full_attention_sites_are_named_and_classed(remat,
                                                               v5e_devices):
    """A model with window and full attention layers of two head counts (the
    Laguna family's toy: 6 and 4 query heads of 64 over 2 KV heads, a window
    of 128 under 512 positions): two shapes of flash call, each kernel
    traced and lowered once a shape; in the compiled step every site is a
    ``tpu_custom_call %flash_fwd`` / ``%flash_bwd`` under its own
    ``block_<i>/mixer``, a window layer's under ``mixer/window`` and the
    backward's under ``transpose(``: what the benchmark's op class
    ``attention_kernel``, its scope classes ``attn_fwd`` / ``attn_bwd`` and
    ``attn_window_kernel_ms``'s own pattern read."""
    from jax.sharding import SingleDeviceSharding

    from chipbench import op_scopes, trace_reduce
    from chipbench.families import laguna
    from chipbench.layer_metrics import attn_gate_ms, attn_window_kernel_ms
    from tests.test_laguna import CONFIG

    op_classes = trace_reduce.load_classes()
    scope_classes = trace_reduce.load_classes(op_scopes.SCOPE_CLASSES)
    config = {**CONFIG, "head_dim": 64, "sliding_window": 128}
    model = laguna.build_model(config, 512, {"remat": remat})
    one = SingleDeviceSharding(v5e_devices[0])
    toks = jax.ShapeDtypeStruct((1, 512), jnp.int32, sharding=one)
    params = jax.tree_util.tree_map(
        lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=one),
        jax.eval_shape(model.init, jax.random.PRNGKey(0),
                       jnp.zeros((1, 512), jnp.int32))["params"])
    with jax.enable_x64(False):
        lowered = jax.jit(jax.grad(lambda p, x: jnp.sum(
            model.apply({"params": p}, x).astype(jnp.float32)))).trace(
                params, toks).lower(lowering_platforms=("tpu",))
    # two head counts, one of them windowed: two bodies a kernel
    assert sorted(re.findall(r'kernel_name = "(flash_\w+)"',
                             lowered.as_text())) == \
        ["flash_bwd"] * 2 + ["flash_fwd"] * 2
    text = lowered.compile().as_text()
    calls = _kernel_calls(text, r"flash_\w+?")
    assert sorted(name for name, _, _ in calls) == \
        ["flash_bwd"] * 5 + ["flash_fwd"] * 5
    windowed = set()
    for name, instruction, path in calls:
        dispatcher, scope = {
            "flash_fwd": ("_flash_fwd_once_call", "attn_fwd"),
            "flash_bwd": ("_flash_bwd_fused", "attn_bwd")}[name]
        block, window = re.search(
            rf"/block_(\d)/mixer/(window/)?jit\({dispatcher}\)/{name}/",
            path).groups()
        assert bool(window) == (config["layer_types"][int(block)]
                                == "sliding_attention"), path
        assert bool(attn_window_kernel_ms.PATTERN.search(path)) \
            == bool(window)
        windowed.add((name, block)) if window else None
        assert ("transpose(" in path) == (name == "flash_bwd"), path
        assert trace_reduce.classify(path, scope_classes) == scope
        assert trace_reduce.classify(
            f"tpu_custom_call %{instruction}", op_classes) \
            == "attention_kernel"
    assert windowed == {(name, str(block)) for name in
                        ("flash_fwd", "flash_bwd") for block in (1, 2, 3)}
    # the gate's projection carries its own scope, in every layer
    gates = set(re.findall(r'op_name="[^"]*/block_(\d)/mixer/gate/[^"]*"',
                           text))
    assert gates == set("01234")
    assert re.search(attn_gate_ms.PATTERN, "x/block_1/mixer/gate/dot_general")
    assert not re.search(attn_gate_ms.PATTERN, "x/block_1/mixer/gate_norm/mul")


@pytest.mark.parametrize("rows,width,block", [(16384, 128, 4),
                                              (768, 128, 12)])
def test_block_diffusion_mask_compiles_for_v5e(rows, width, block,
                                               v5e_devices):
    """``sdarmoe-train-s8192``'s layer (16,384 rows, 32 heads of 128, blocks
    of 4: K and V resident, a dq scratch that asks for its own limit, the
    tables of live tiles behind the offsets in the scalar prefetch), and a
    block length that is no power of two and that the tiles' edges cut
    (an integer division on a column and a row): forward and backward."""
    from horovod_tpu.ops import pallas_kernels as pk

    mesh = Mesh(np.array(v5e_devices[:1]), ("hvd",))
    heads = 32 if rows > 1024 else 4
    q = jax.ShapeDtypeStruct((1, rows, heads, width), jnp.bfloat16)
    route = pk.flash_route(rows, rows, width, 2)
    assert (route["forward"], route["backward"]) == ("once", "fused")
    grads = jax.grad(lambda q, k, v: jnp.sum(pk.flash_attention(
        q, k, v, causal=True, block_diffusion=block).astype(jnp.float32)),
        argnums=(0, 1, 2))
    lowered = lower_tpu(grads, *on_mesh([q, q, q], mesh))
    assert sorted(re.findall(r'kernel_name = "(flash_\w+)"',
                             lowered.as_text())) == ["flash_bwd", "flash_fwd"]
    lowered.compile()


@pytest.mark.parametrize("remat", ["none", "full"])
def test_block_diffusion_sites_are_named_and_classed(remat, v5e_devices):
    """A model built to denoise by blocks (the SDAR family's toy at 1,024
    data positions, 2,048 rows: two key tiles a head): in the compiled step
    every site is a ``tpu_custom_call %flash_fwd`` / ``%flash_bwd`` under
    its own ``block_<i>/mixer/block_diffusion``, the backward's under
    ``transpose(``: what the benchmark's op class ``attention_kernel``, its
    scope classes ``attn_fwd`` / ``attn_bwd`` and
    ``attn_blockdiff_relayout_ms``'s own pattern read; the halves are laid
    side by side and cut apart under ``denoise_io``."""
    from jax.sharding import SingleDeviceSharding

    from chipbench import op_scopes, trace_reduce
    from chipbench.families import sdar_moe
    from chipbench.layer_metrics import (attn_blockdiff_relayout_ms,
                                         denoise_io_ms)
    from tests.test_sdar_moe import CONFIG

    op_classes = trace_reduce.load_classes()
    scope_classes = trace_reduce.load_classes(op_scopes.SCOPE_CLASSES)
    model = sdar_moe.build_model(CONFIG, 512, {"remat": remat})
    one = SingleDeviceSharding(v5e_devices[0])
    ids = jnp.zeros((1, 1024), jnp.int32)
    toks = jax.ShapeDtypeStruct(ids.shape, ids.dtype, sharding=one)
    params = jax.tree_util.tree_map(
        lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=one),
        jax.eval_shape(model.init, jax.random.PRNGKey(0), ids,
                       ids)["params"])
    with jax.enable_x64(False):
        lowered = jax.jit(jax.grad(lambda p, x, y: jnp.sum(
            model.apply({"params": p}, x, y).astype(jnp.float32)))).trace(
                params, toks, toks).lower(lowering_platforms=("tpu",))
    # one shape of flash call: one body a kernel
    assert sorted(re.findall(r'kernel_name = "(flash_\w+)"',
                             lowered.as_text())) == ["flash_bwd", "flash_fwd"]
    text = lowered.compile().as_text()
    calls = _kernel_calls(text, r"flash_\w+?")
    assert sorted(name for name, _, _ in calls) == \
        ["flash_bwd"] * 2 + ["flash_fwd"] * 2
    for name, instruction, path in calls:
        dispatcher, scope = {
            "flash_fwd": ("_flash_fwd_once_call", "attn_fwd"),
            "flash_bwd": ("_flash_bwd_fused", "attn_bwd")}[name]
        assert re.search(
            rf"/block_\d/mixer/block_diffusion/jit\({dispatcher}\)/{name}/",
            path), path
        assert attn_blockdiff_relayout_ms.PATTERN.search(path)
        assert ("transpose(" in path) == (name == "flash_bwd"), path
        assert trace_reduce.classify(path, scope_classes) == scope
        assert trace_reduce.classify(
            f"tpu_custom_call %{instruction}", op_classes) \
            == "attention_kernel"
    assert re.search(r'op_name="[^"]*/denoise_io/[^"]*"', text)
    assert re.search(denoise_io_ms.PATTERN, "jit(f)/HybridLM/denoise_io/slice")
    assert not re.search(denoise_io_ms.PATTERN, "jit(f)/denoise_io_x/slice")


# the forward a head's K and V take -> its kernel and its dispatcher: the
# cell's (resident since PR 44), and the streaming one a head past
# ``_KV_VMEM_CAP`` takes, here by a cap of 1
_LATENT_FORWARDS = {
    "once": ("flash_fwd", "_flash_fwd_once_call"),
    "step_streaming": ("flash_step", "_flash_step_call_streaming"),
}


@pytest.mark.parametrize("forward", sorted(_LATENT_FORWARDS))
def test_latent_attention_sites_are_named_and_classed(forward, v5e_devices,
                                                      monkeypatch):
    """A model whose every layer is latent attention at the published head
    widths (2 heads, keys 128 + 64, values 128, a latent of 64; 4096
    positions, where dq still fits its scratch), recomputed as the cell's:
    in the compiled step every site is a ``tpu_custom_call %flash_fwd``
    (``%flash_step`` where K and V stream) / ``%flash_bwd`` under its own
    ``block_<i>/mixer``, the backward's under ``transpose(``, which is what
    the benchmark's op class ``attention_kernel`` and its scope classes
    ``attn_fwd`` / ``attn_bwd`` read; the latent's products and the
    assembling of q and k carry the scopes ``mla_latent_ms`` and
    ``mla_assemble_ms`` read."""
    from jax.sharding import SingleDeviceSharding

    from chipbench import op_scopes, trace_reduce
    from chipbench.families import deepseek_v3
    from chipbench.layer_metrics import mla_assemble_ms, mla_latent_ms
    from horovod_tpu.ops import pallas_kernels as pk
    from tests.test_kanana import CONFIG

    if forward == "step_streaming":
        monkeypatch.setattr(pk, "_KV_VMEM_CAP", 1)
    pk._flash_fullattn_vjp.cache_clear()
    assert pk.flash_route(4096, 4096, 192, 2, dv=128)["forward"] == forward
    fwd_kernel, fwd_dispatcher = _LATENT_FORWARDS[forward]
    op_classes = trace_reduce.load_classes()
    scope_classes = trace_reduce.load_classes(op_scopes.SCOPE_CLASSES)
    config = {**CONFIG, "num_attention_heads": 2, "num_key_value_heads": 2,
              "kv_lora_rank": 64, "qk_nope_head_dim": 128,
              "qk_rope_head_dim": 64, "qk_head_dim": 192, "v_head_dim": 128}
    seq, layers = 4096, config["num_hidden_layers"]
    model = deepseek_v3.build_model(config, 512, {"remat": "full"})
    one = SingleDeviceSharding(v5e_devices[0])
    toks = jax.ShapeDtypeStruct((1, seq), jnp.int32, sharding=one)
    params = jax.tree_util.tree_map(
        lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=one),
        jax.eval_shape(model.init, jax.random.PRNGKey(0),
                       jnp.zeros((1, seq), jnp.int32))["params"])
    with jax.enable_x64(False):
        lowered = jax.jit(jax.grad(lambda p, x: jnp.sum(
            model.apply({"params": p}, x).astype(jnp.float32)))).trace(
                params, toks).lower(lowering_platforms=("tpu",))
    # one shape of call: one body a kernel, whatever the depth
    pk._flash_fullattn_vjp.cache_clear()
    assert sorted(re.findall(r'kernel_name = "(flash_\w+)"',
                             lowered.as_text())) == sorted(
                                 ["flash_bwd", fwd_kernel])
    text = lowered.compile().as_text()
    calls = _kernel_calls(text, r"flash_\w+?")
    assert sorted(name for name, _, _ in calls) == sorted(
        ["flash_bwd"] * layers + [fwd_kernel] * layers)
    for name, instruction, path in calls:
        dispatcher, scope = {
            fwd_kernel: (fwd_dispatcher, "attn_fwd"),
            "flash_bwd": ("_flash_bwd_fused", "attn_bwd")}[name]
        assert re.search(rf"/block_(\d)/mixer/jit\({dispatcher}\)/{name}/",
                         path), path
        assert ("transpose(" in path) == (name == "flash_bwd"), path
        assert trace_reduce.classify(path, scope_classes) == scope
        assert trace_reduce.classify(
            f"tpu_custom_call %{instruction}", op_classes) \
            == "attention_kernel"
    for reader, part in ((mla_latent_ms, "latent"),
                         (mla_assemble_ms, "assemble")):
        paths = re.findall(rf'op_name="([^"]*/mixer/{part}[/"][^"]*)"', text)
        assert {re.search(r"block_(\d)", p).group(1) for p in paths} \
            == set("012"), part
        assert all(re.search(reader.PATTERN, p) for p in paths)
        assert any("transpose(" in p for p in paths)


def test_gated_delta_and_gated_attention_sites_are_named_and_classed(
        v5e_devices):
    """One period of the Qwen3-Next family at its toy widths (three
    delta-rule layers, then gated attention at head 64; 512 positions, eight
    chunks a layer), recomputed as the cell's and compiled for a v5e: every
    operation of the chunked rule, the scan over chunks among them, carries
    ``block_<i>/mixer/delta_rule`` in the forward pass, the recomputed one
    and the backward, which ``delta_rule_ms`` reads and the scope classes
    book to the blocks; the conv, the L2 norms and the decays carry
    ``mixer/prep``; the attention layer's gate ``mixer/gate`` as Laguna's,
    and its flash calls are classed as every cell's; the shared expert's
    gate is ``ffn/shared_gate``, in every block."""
    from chipbench import op_scopes, trace_reduce
    from chipbench.families import qwen3_next
    from chipbench.layer_metrics import (attn_gate_ms, delta_rule_ms,
                                         delta_rule_prep_ms, moe_shared_ms)
    from tests.test_qwen3_next import CONFIG

    op_classes = trace_reduce.load_classes()
    scope_classes = trace_reduce.load_classes(op_scopes.SCOPE_CLASSES)
    model = qwen3_next.build_model(CONFIG, 512, {"remat": "full"})
    text = _model_grad_text(model, 512, v5e_devices)
    paths = re.findall(r'op_name="([^"]*)"', text)
    # (the scan's own checkpoint leaves a ``rematted_computation`` *behind*
    # the block's name: a chunk run again inside the rule's backward pass,
    # which is the backward pass's)
    def recomputed(p):
        return bool(re.search(r"rematted_computation/block_\d", p))

    passes = {"blocks_fwd": lambda p: "transpose(" not in p,
              "blocks_recompute": recomputed,
              "blocks_bwd": lambda p: "transpose(" in p and not recomputed(p)}
    for reader, part, blocks in ((delta_rule_ms, "delta_rule", "012"),
                                 (delta_rule_prep_ms, "prep", "012"),
                                 (attn_gate_ms, "gate", "3")):
        under = [p for p in paths if f"/mixer/{part}/" in p + "/"]
        assert {re.search(r"block_(\d)", p).group(1) for p in under} \
            == set(blocks), part
        assert all(re.search(reader.PATTERN, p) for p in under)
        classes = {trace_reduce.classify(p, scope_classes) for p in under}
        assert classes == set(passes), (part, classes)
        for name, holds in passes.items():
            assert all(holds(p) for p in under
                       if trace_reduce.classify(p, scope_classes) == name)
    # the scan over chunks is inside the scope, a while loop of the program
    assert any("/mixer/delta_rule/" in p and "while" in p for p in paths)
    # no reader of another family's part matches the new ones
    assert not [p for p in paths if "/mixer/gate_norm" in p
                and re.search(attn_gate_ms.PATTERN, p)]
    gates = [p for p in paths if "/ffn/shared_gate/" in p]
    assert {re.search(r"block_(\d)", p).group(1) for p in gates} \
        == set("0123")
    assert not [p for p in gates if re.search(moe_shared_ms.PATTERN, p)]
    assert {trace_reduce.classify(p, scope_classes) for p in gates} \
        <= set(passes)
    calls = _kernel_calls(text, r"flash_\w+?")
    assert sorted(name for name, _, _ in calls) == ["flash_bwd", "flash_fwd"]
    for name, instruction, path in calls:
        assert "/block_3/mixer/jit(" in path, path
        assert trace_reduce.classify(path, scope_classes) == {
            "flash_fwd": "attn_fwd", "flash_bwd": "attn_bwd"}[name]
        assert trace_reduce.classify(
            f"tpu_custom_call %{instruction}", op_classes) \
            == "attention_kernel"


@pytest.mark.parametrize("remat", ["none", "full"])
def test_delta_rule_kernels_sit_under_the_delta_rule_scope(remat,
                                                           v5e_devices):
    """One period of the Qwen3-Next family with heads the route admits
    (four value heads of 128 / 128 over two key heads; 512 positions, two
    tiles a head cell), compiled for a v5e: a delta-rule layer's rule is
    ``delta_fwd`` in the forward pass, again in a recomputed block
    (``remat="full"`` keeps a block's input; the recomputed call is the
    one that saves the tiles' entering states and the chunks' inverses)
    and ``delta_bwd``; every one of them sits under
    ``block_<i>/mixer/delta_rule``, the backward kernel too, which
    ``delta_rule_ms`` and ``delta_rule_roofline`` read as they are (a
    kernel outside the scope would read as a gain) and the scope classes
    book to the blocks' three passes; and nothing of the XLA form is left:
    no loop over chunks, no chunk's system or state in HBM."""
    from chipbench import op_scopes, trace_reduce
    from chipbench.families import qwen3_next
    from chipbench.layer_metrics import delta_rule_ms
    from horovod_tpu.ops import pallas_kernels as pk
    from tests.test_qwen3_next import CONFIG

    config = dict(CONFIG, linear_key_head_dim=128, linear_value_head_dim=128)
    operand = jax.ShapeDtypeStruct((1, 512, 4, 128), jnp.bfloat16)
    assert pk.kernel_path("gated_delta", operand, operand, operand) \
        == "pallas"
    scope_classes = trace_reduce.load_classes(op_scopes.SCOPE_CLASSES)
    model = qwen3_next.build_model(config, 512, {"remat": remat})
    text = _model_grad_text(model, 512, v5e_devices)
    calls = _kernel_calls(text, "delta_fwd|delta_bwd")
    layers, forwards = 3, 2 if remat == "full" else 1
    assert sorted(name for name, _, _ in calls) == \
        ["delta_bwd"] * layers + ["delta_fwd"] * layers * forwards
    for name, _, path in calls:
        assert re.search(delta_rule_ms.PATTERN, path), path
        block, = re.findall(
            r"block_(\d)/mixer/delta_rule/jit\(_" + name + r"\)/" + name,
            path)
        assert block in "012"
        recomputed = bool(re.search(r"rematted_computation/block_\d", path))
        assert ("transpose(" in path) == (name == "delta_bwd" or recomputed)
        assert trace_reduce.classify(path, scope_classes) == (
            "blocks_recompute" if recomputed else
            "blocks_bwd" if name == "delta_bwd" else "blocks_fwd")
    for block in range(layers):
        assert sum(f"block_{block}/" in path for _, _, path in calls) \
            == 1 + forwards
    assert sum("rematted_computation" in path for _, _, path in calls) \
        == (layers if remat == "full" else 0)
    # what XLA keeps inside the scope is there in every pass, and no loop
    paths = re.findall(r'op_name="([^"]*/mixer/delta_rule/[^"]*)"', text)
    assert not [p for p in paths if "while" in p]
    assert {trace_reduce.classify(p, scope_classes) for p in paths} == {
        "blocks_fwd", "blocks_bwd"} | ({"blocks_recompute"}
                                       if remat == "full" else set())
    # the saved states a tile and inverses a pair, and no chunk's system
    assert "f32[1,2,4,128,128]" in text and "f32[1,4,256,128]" in text
    assert not re.search(r"f32\[\d+,1,4,64,64\]", text)
