"""The scope reader: the wire format on hand-made messages, the recorded
v5e traces (PR 22's, of a program without this PR's scopes, and the same
rehearsal model re-recorded with them), and the scope classes."""

import gzip
import importlib
import os

import pytest

from chipbench import harness, op_scopes
from chipbench import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
UNSCOPED = os.path.join(DATA, "tiny_train_v5e.xplane.pb.gz")
SCOPED = os.path.join(DATA, "tiny_train_scoped_v5e.xplane.pb.gz")
XLA_OP_SPLIT = ["blocks_fwd_ms", "blocks_bwd_ms", "blocks_recompute_ms",
                "head_loss_ms", "optimizer_ms", "model_other_ms"]
KERNEL_SPLIT = ["attn_fwd_kernel_ms", "attn_bwd_kernel_ms"]


# ----------------------------------------------------- hand-made messages
def varint(n: int) -> bytes:
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def field(number: int, value) -> bytes:
    """A varint field for an int, a length-delimited one for bytes/str."""
    if isinstance(value, int):
        return varint(number << 3) + varint(value)
    value = value.encode() if isinstance(value, str) else value
    return varint(number << 3 | 2) + varint(len(value)) + value


def entry(key: int, value: bytes) -> bytes:
    return field(1, key) + field(2, value)


def test_fields_reads_varints_and_skips_what_it_does_not_parse():
    message = (field(1, 5) + field(300, 1 << 40)            # two-byte tag
               + varint(7 << 3 | 1) + b"\x01" * 8           # fixed64
               + varint(8 << 3 | 5) + b"\x02" * 4           # fixed32
               + field(3, "x" * 200)                        # two-byte length
               + field(2, b""))
    got = [(n, v if isinstance(v, int) else bytes(v))
           for n, v in op_scopes.fields(memoryview(message))]
    assert got == [(1, 5), (300, 1 << 40), (7, b"\x01" * 8),
                   (8, b"\x02" * 4), (3, b"x" * 200), (2, b"")]
    with pytest.raises(ValueError, match="wire type 3"):
        list(op_scopes.fields(varint(1 << 3 | 3)))           # a group
    with pytest.raises(ValueError, match="past the message's end"):
        list(op_scopes.fields(field(3, "abcdef")[:-2]))


def hand_made_plane(name: str, ops) -> bytes:
    """A plane with a long line to pass over, stat names 1 ``tf_op``, 2
    ``flops`` and 3 (a string kept as a stat's name), and one event metadata
    an op: ``(instruction text, stat fields)``."""
    stats = {1: "tf_op", 2: "flops", 3: "jit(step)/optimizer/mul:"}
    plane = field(2, name) + field(3, b"\xff" * 1000)
    for i, text in stats.items():
        plane += field(5, entry(i, field(1, i) + field(2, text)))
    for i, (text, stat_fields) in enumerate(ops):
        meta = field(1, i) + field(2, text)
        for stat in stat_fields:
            meta += field(5, stat)
        plane += field(4, entry(i, meta))
    return plane


def test_read_takes_tf_op_from_the_event_metadata(tmp_path):
    fusion = "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop"
    kernel = ('%flash_fwd.2 = bf16[2]{0} custom-call(bf16[2]{0} %q), '
              'custom_call_target="tpu_custom_call"')
    ops = [(fusion, [field(1, 2) + field(4, 99),               # flops first
                     field(1, 1) + field(5, "jit(step)/jvp(loss)/exp:")]),
           (kernel, [field(1, 1) + field(
               5, "jit(step)/jvp(TransformerLM)/block_0/flash_fwd/"
                  "flash_fwd/pallas_call:")]),
           ("%mul.3 = f32[] multiply(f32[] %a, f32[] %b)",
            [field(1, 1) + field(7, 3)]),                       # by reference
           ("%copy.4 = f32[8]{0} copy(f32[8]{0} %x)", [])]
    space = (field(1, hand_made_plane("/device:TPU:0", ops))
             + field(1, hand_made_plane("/device:TPU:1", ops[3:]))
             + field(1, hand_made_plane("/host:CPU", ops)))
    path = tmp_path / "hand.xplane.pb"
    path.write_bytes(space)
    assert op_scopes.read(str(path)) == {"/device:TPU:0": {
        "fusion %fusion.1": "jit(step)/jvp(loss)/exp",
        "tpu_custom_call %flash_fwd.2":
            "jit(step)/jvp(TransformerLM)/block_0/flash_fwd/flash_fwd/"
            "pallas_call",
        "multiply %mul.3": "jit(step)/optimizer/mul",
        "copy %copy.4": ""}}          # TPU:1 holds no scope: left out
    gz = tmp_path / "hand.xplane.pb.gz"
    gz.write_bytes(gzip.compress(space))
    assert op_scopes.read(str(gz)) == op_scopes.read(str(path))


def test_read_falls_back_to_the_hlo_proto_where_no_plane_has_tf_op(tmp_path):
    """The CPU backend: events are named ``dot.6`` and the scope path is in
    the program's HloProto on the ``/host:metadata`` plane."""
    def instruction(name, op_name=None):
        inst = field(1, name) + field(2, "dot")
        if op_name:
            inst += field(7, field(1, "dot_general") + field(2, op_name))
        return field(2, inst)

    scope = "jit(step)/jvp(TransformerLM)/block_1/mlp_in/dot_general"
    computation = (field(1, "main") + instruction("dot.6", scope)
                   + instruction("copy.9"))
    proto = field(1, field(1, "jit_step") + field(3, computation))
    metadata = (field(2, "/host:metadata")
                + field(5, entry(1, field(1, 1) + field(2, "Hlo Proto")))
                + field(4, entry(12, field(1, 12) + field(2, "jit_step(12)")
                                 + field(5, field(1, 1) + field(6, proto)))))
    path = tmp_path / "cpu.xplane.pb"
    path.write_bytes(field(1, field(2, "/host:CPU") + field(3, b"\0" * 64))
                     + field(1, metadata))
    assert op_scopes.read(str(path)) == {"/host:CPU": {"dot dot.6": scope}}
    assert tr.op_name("dot.6") == "dot dot.6"


# ----------------------------------------------------------- scope classes
T = "jit(step)/transpose(jvp(TransformerLM))"
F = "jit(step)/jvp(TransformerLM)"


@pytest.mark.parametrize("path,scope_class", [
    (f"{F}/block_0/qkv/dot_general", "blocks_fwd"),
    ("jit(step)/shard_map/jvp(TransformerLM)/block_23/mul", "blocks_fwd"),
    (f"{T}/block_11/mlp_in/dot_general", "blocks_bwd"),
    # remat="full": the backward pass, and the forward it runs again
    (f"{T}/jvp(TransformerLM)/checkpoint/block_3/ln_mlp/mul", "blocks_bwd"),
    (f"{T}/jvp(TransformerLM)/checkpoint/rematted_computation/block_3/tanh",
     "blocks_recompute"),
    (f"{F}/block_0/flash_fwd/flash_fwd/pallas_call", "attn_fwd"),
    (f"{T}/jvp(TransformerLM)/checkpoint/rematted_computation/block_3/"
     "flash_fwd/flash_fwd/pallas_call", "attn_fwd"),
    ("jit(step)/ring/flash_step/flash_step/pallas_call", "attn_fwd"),
    (f"{T}/block_0/flash_bwd/flash_bwd/pallas_call", "attn_bwd"),
    ("jit(f)/transpose(jvp(flash_bwd_dkv))/flash_bwd_dkv/pallas_call",
     "attn_bwd"),
    (f"{T}/block_0/flash_bwd_dq/flash_bwd_dq/pallas_call", "attn_bwd"),
    (f"{F}/tok_emb.attend/dot_general", "head_loss"),
    (f"{T}/tok_emb.attend/dot_general", "head_loss"),
    ("jit(step)/jvp(loss)/jit(log_softmax)/exp", "head_loss"),
    ("jit(step)/transpose(jvp(loss))/jit(take_along_axis)/scatter-add",
     "head_loss"),
    ("jit(step)/optimizer/mul", "optimizer"),
    ("jit(step)/shard_map/optimizer/add", "optimizer"),
    (f"{F}/tok_emb/jit(_take)/gather", "other"),
    (f"{T}/ln_f/mul", "other"),
    ("jit(step)/shard_map/grad_allreduce/psum", "other"),
    ("jit(step)/my_block_0/loss_scale/optimizer_state/mul", "other"),
    ("", "other"),
])
def test_scope_classes(path, scope_class):
    classes = tr.load_classes(op_scopes.SCOPE_CLASSES)
    assert tr.classify(path, classes) == scope_class
    assert all(os.path.exists(os.path.join(
        op_scopes.SCOPE_CLASSES, f"{name}.json")) for name, _ in classes)


# --------------------------------------------------------- recorded traces
def window_on(path, tmp_path, monkeypatch, units=15) -> harness.Window:
    """A traced run's window whose trace is the recorded file."""
    with gzip.open(path, "rb") as f:
        (tmp_path / "recorded.xplane.pb").write_bytes(f.read())
    monkeypatch.setattr(harness, "TRACE_DIR", str(tmp_path))
    return harness.Window(
        cell=None, peak={}, correct=True, attempted=0, failed=0,
        end_to_end={}, measured={}, counters={}, first_calls=[],
        memory_peak_bytes=0, trace=tr.summarize(tr.read_xplane(path), units))


def read_metrics(window, names):
    return {name: importlib.import_module(
        f"chipbench.layer_metrics.{name}").read(window) for name in names}


def test_every_operation_of_the_recorded_trace_finds_its_metadata():
    scopes = op_scopes.read(UNSCOPED)
    assert sorted(scopes) == ["/device:TPU:0"]
    scopes = scopes["/device:TPU:0"]
    first = tr.summarize(tr.read_xplane(UNSCOPED), 15).first
    assert len(scopes) == 499 and set(first.op_s) <= set(scopes)
    assert sum(1 for s in scopes.values() if s) == 132

    def covered(op_class):
        ops = [op for op in first.op_s if first.op_class[op] == op_class]
        return (sum(first.op_s[op] for op in ops if scopes[op])
                / sum(first.op_s[op] for op in ops))

    # PR 22's kernels carry no name: Pallas calls no class file claims
    assert "attention_kernel" not in first.op_class.values()
    assert all(scopes[op] for op in first.op_s
               if op.startswith("tpu_custom_call "))
    assert covered("xla_op") > 0.99
    assert covered("copy") == pytest.approx(0.206, abs=0.001)
    qkv = {s.rsplit("/", 1)[0] for s in scopes.values()
           if "/block_0/qkv/" in s}
    assert qkv == {"jit(step)/jvp(TransformerLM)/block_0/qkv",
                   "jit(step)/transpose(jvp(TransformerLM))/block_0/qkv"}


def test_a_program_without_the_scopes_leaves_their_metrics_out(
        tmp_path, monkeypatch):
    """PR 22's trace, of the parent's program: Flax's module names are
    there, ``optimizer``, ``loss`` and the kernels' names are not."""
    window = window_on(UNSCOPED, tmp_path, monkeypatch)
    got = read_metrics(window, XLA_OP_SPLIT + KERNEL_SPLIT)
    assert [n for n, v in got.items() if v is None] == [
        "blocks_recompute_ms", "optimizer_ms", "attn_fwd_kernel_ms",
        "attn_bwd_kernel_ms"]
    assert sum(v for n, v in got.items() if v is not None) == pytest.approx(
        window.trace.ms_per_unit("class_s", "xla_op"))
    assert (got["blocks_bwd_ms"] > got["blocks_fwd_ms"]
            > got["head_loss_ms"] > 0)
    window.trace = None                               # an untraced run
    assert set(read_metrics(window, XLA_OP_SPLIT + KERNEL_SPLIT).values()) \
        == {None}


def test_scoped_trace_splits_both_classes_exactly(tmp_path, monkeypatch):
    """The same rehearsal model (2 layers, width 128, batch 2 x 128, 15
    steps) traced on a TPU v5 lite in PR 24's chip run, with this PR's
    scopes in the program: the file's numbers, as the reader read them
    then, and the two identities the chip runs are held to."""
    window = window_on(SCOPED, tmp_path, monkeypatch)
    got = read_metrics(window, XLA_OP_SPLIT + KERNEL_SPLIT)
    assert got.pop("blocks_recompute_ms") is None     # no remat in it
    want = {"blocks_fwd_ms": 0.00705433, "blocks_bwd_ms": 0.01281487,
            "head_loss_ms": 0.00748193, "optimizer_ms": 0.00167960,
            "model_other_ms": 0.00473520, "attn_fwd_kernel_ms": 0.00448967,
            "attn_bwd_kernel_ms": 0.00507100}
    assert got == {k: pytest.approx(v, rel=1e-5) for k, v in want.items()}
    t = window.trace
    assert sum(got[n] for n in XLA_OP_SPLIT if n in got) == pytest.approx(
        t.ms_per_unit("class_s", "xla_op"), rel=1e-9)
    assert sum(got[n] for n in KERNEL_SPLIT) == pytest.approx(
        t.ms_per_unit("class_s", "attention_kernel"), rel=1e-9)
    # the kernels carry their names, each direction once a layer
    first = t.first
    assert sorted(op for op in first.op_s
                  if first.op_class[op] == "attention_kernel") == [
        "tpu_custom_call %flash_bwd.2", "tpu_custom_call %flash_bwd.3",
        "tpu_custom_call %flash_fwd.2", "tpu_custom_call %flash_fwd.3"]
    scopes = op_scopes.read(SCOPED)["/device:TPU:0"]
    assert scopes["tpu_custom_call %flash_bwd.2"].endswith(
        "/flash_bwd/flash_bwd/pallas_call")
    by = op_scopes.scope_ms(window)
    assert by["copy", "other"] == pytest.approx(0.0131882, rel=1e-5)
    assert sum(by.values()) == pytest.approx(1e3 * first.busy_s / 15)


def test_a_trace_with_no_scope_reads_nothing(tmp_path, monkeypatch):
    window = window_on(UNSCOPED, tmp_path, monkeypatch)
    (tmp_path / "recorded.xplane.pb").write_bytes(
        field(1, hand_made_plane("/device:TPU:0", [("%copy.4 = x", [])])))
    assert op_scopes.scope_ms(window) == {}
    assert set(read_metrics(window, XLA_OP_SPLIT + KERNEL_SPLIT).values()) \
        == {None}
