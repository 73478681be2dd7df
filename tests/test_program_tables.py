"""Every instruction of a compiled program under the name a trace prints
for it: ``hlo_collectives.instructions`` on a literal text and on small
programs compiled here, ``ProgramTable``'s roles and edges, and the
tables the engine builds of its own programs, which cost nothing until
somebody asks."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from megatron_llm_tpu import hlo_collectives as H

# an optimised module as the TPU's compiler prints one, cut to what the
# reader has to tell apart
TEXT = r"""
HloModule jit_step, is_scheduled=true

%region_add (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %add.1 = f32[] add(%a, %b)
}

%fused_mostly_mlp (p0: f32[8,16]) -> f32[8,16] {
  %p0 = f32[8,16]{1,0:T(8,128)} parameter(0)
  %mul.1 = f32[8,16]{1,0} multiply(%p0, %p0), metadata={op_name="jit(step)/mlp/mul" stack_frame_id=3}
  %neg.1 = f32[8,16]{1,0} negate(%mul.1), metadata={op_name="jit(step)/mlp/jit(silu)/neg"}
  %convert.9 = f32[8,16]{1,0} convert(%neg.1)
  ROOT %add.2 = f32[8,16]{1,0} add(%convert.9, %p0), metadata={op_name="jit(step)/attention/add"}
}

%fused_tie (p0: f32[8,16]) -> f32[8,16] {
  %p0.1 = f32[8,16]{1,0} parameter(0)
  %mul.2 = f32[8,16]{1,0} multiply(%p0.1, %p0.1), metadata={op_name="jit(step)/mlp/mul"}
  ROOT %add.3 = f32[8,16]{1,0} add(%mul.2, %p0.1), metadata={op_name="jit(step)/attention/kv_write/add"}
}

%fused_copy (p0: bf16[9,4,2,8]) -> bf16[9,4,2,8] {
  %p0.2 = bf16[9,4,2,8]{3,2,1,0} parameter(0)
  ROOT %copy.7 = bf16[9,4,2,8]{3,2,1,0:T(8,128)(2,1)} copy(%p0.2)
}

%wrapped_slice (p0.3: bf16[9,4,2,8]) -> bf16[4,4,2,8] {
  %p0.3 = bf16[9,4,2,8]{3,2,1,0} parameter(0)
  ROOT %slice.3 = bf16[4,4,2,8]{3,2,1,0} slice(%p0.3), slice={[5:9], [0:4], [0:2], [0:8]}
}

%wrapped_gather (p0.4: f32[8,16]) -> f32[16,16] {
  %p0.4 = f32[8,16]{1,0} parameter(0)
  ROOT %all-gather.9 = f32[16,16]{1,0} all-gather(%p0.4), channel_id=3, replica_groups={{0,1},{2,3}}, dimensions={0}
}

%branch_sort (t: (f32[8,16])) -> (f32[8,16]) {
  %t = (f32[8,16]{1,0}) parameter(0)
  %gte.1 = f32[8,16]{1,0} get-tuple-element(%t), index=0
  %sort.1 = f32[8,16]{1,0} sort(%gte.1), dimensions={1}, to_apply=%region_add, metadata={op_name="jit(step)/sampler/cond/branch_1_fun/sort"}
  ROOT %tuple.1 = (f32[8,16]{1,0}) tuple(%sort.1)
}

%branch_pass (t.1: (f32[8,16])) -> (f32[8,16]) {
  ROOT %t.1 = (f32[8,16]{1,0}) parameter(0)
}

%body (c: (s32[], f32[8,16])) -> (s32[], f32[8,16]) {
  %c = (s32[], f32[8,16]{1,0}) parameter(0)
  %i = s32[] get-tuple-element(%c), index=0
  %x = f32[8,16]{1,0} get-tuple-element(%c), index=1
  %fusion.5 = f32[8,16]{1,0} fusion(%x), kind=kLoop, calls=%fused_mostly_mlp, metadata={op_name="jit(step)/transformer_layer/attention/add"}
  %psum.7 = f32[8,16]{1,0} all-reduce(%fusion.5), channel_id=1, replica_groups=[2,2]<=[2,2]T(1,0), use_global_device_ids=true, to_apply=%region_add, metadata={op_name="jit(step)/transformer_layer/psum"}
  %all-gather-start.1 = (f32[8,16]{1,0}, f32[16,16]{1,0}) all-gather-start(%psum.7), channel_id=2, replica_groups={{0,1},{2,3}}, dimensions={0}
  %all-gather-done.1 = f32[16,16]{1,0} all-gather-done(%all-gather-start.1)
  ROOT %tuple.2 = (s32[], f32[8,16]{1,0}) tuple(%i, %psum.7)
}

%cond (c.1: (s32[], f32[8,16])) -> pred[] {
  %c.1 = (s32[], f32[8,16]{1,0}) parameter(0)
  %i.1 = s32[] get-tuple-element(%c.1), index=0
  %limit = s32[] constant(5)
  ROOT %lt = pred[] compare(%i.1, %limit), direction=LT
}

ENTRY %main.9 (k_pages: bf16[9,4,2,8], x.1: f32[8,16]) -> (f32[8,16], bf16[9,4,2,8]) {
  %k_pages = bf16[9,4,2,8]{3,2,1,0:T(8,128)(2,1)} parameter(0), metadata={op_name="args[1][0]['k_pages']"}
  %x.1 = f32[8,16]{1,0} parameter(1)
  %slice-start.4 = ((bf16[9,4,2,8]{3,2,1,0}), bf16[5,4,2,8]{3,2,1,0:S(1)}, s32[]{:S(2)}) slice-start(%k_pages), slice={[0:5], [0:4], [0:2], [0:8]}
  %slice-done.4 = bf16[5,4,2,8]{3,2,1,0:S(1)} slice-done(%slice-start.4)
  %slice-start.5 = ((bf16[9,4,2,8]{3,2,1,0}), bf16[4,4,2,8]{3,2,1,0:S(1)}, s32[]{:S(2)}) async-start(%k_pages), calls=%wrapped_slice
  %slice-done.5 = bf16[4,4,2,8]{3,2,1,0:S(1)} async-done(%slice-start.5)
  %all-gather-start.9 = ((f32[8,16]{1,0}), f32[16,16]{1,0}) async-start(%x.1), calls=%wrapped_gather
  %all-gather-done.9 = f32[16,16]{1,0} async-done(%all-gather-start.9)
  %copy.800 = bf16[9,4,2,8]{3,2,1,0} copy(%k_pages), backend_config={"flag_configs":[],"window_config":{"kernel_window_bounds":[]}}
  %fusion.6 = bf16[9,4,2,8]{3,2,1,0} fusion(%copy.800), kind=kLoop, calls=%fused_copy
  %copy.801 = f32[8,16]{1,0} copy(%x.1), metadata={op_name="jit(step)/embedding/copy"}
  %fusion.7 = f32[8,16]{1,0} fusion(%copy.801), kind=kLoop, calls=%fused_tie
  %zero = s32[] constant(0)
  %tuple.3 = (s32[], f32[8,16]{1,0}) tuple(%zero, %fusion.7)
  %while.1 = (s32[], f32[8,16]{1,0}) while(%tuple.3), condition=%cond, body=%body, backend_config={"known_trip_count":{"n":"5"}}
  %gte.2 = f32[8,16]{1,0} get-tuple-element(%while.1), index=1
  %tuple.4 = (f32[8,16]{1,0}) tuple(%gte.2)
  %pick = pred[] constant(true)
  %conditional.1 = (f32[8,16]{1,0}) conditional(%pick, %tuple.4, %tuple.4), true_computation=%branch_sort, false_computation=%branch_pass
  %gte.3 = f32[8,16]{1,0} get-tuple-element(%conditional.1), index=0
  ROOT %tuple.5 = (f32[8,16]{1,0}, bf16[9,4,2,8]{3,2,1,0}) tuple(%gte.3, %fusion.6)
}
"""

POOL = [("bfloat16", (9, 4, 2, 8))]
MESH = {"dp": 2, "tp": 2}


@pytest.fixture(scope="module")
def rows():
    return {r["name"]: r for r in H.instructions(TEXT)}


def test_a_row_an_instruction_under_the_name_a_trace_prints(rows):
    # no % in a name; a fusion's body and a reducer's are no rows
    assert "copy.800" in rows and "%copy.800" not in rows
    assert not {"mul.1", "add.2", "copy.7", "add.1", "a"} & set(rows)
    c = rows["copy.800"]
    assert (c["opcode"], c["dtype"], c["shape"], c["bytes"]) == (
        "copy", "bf16", (9, 4, 2, 8), 9 * 4 * 2 * 8 * 2)
    assert c["operands"] == ["k_pages"] and c["computation"] == "main.9"
    assert (c["loops"], c["under"], c["calls"]) == ((), (), 1)
    # the metadata string; "" where the compiler made the instruction
    assert c["op_name"] == "" and c["scope"] == ""
    assert rows["k_pages"]["op_name"] == "args[1][0]['k_pages']"
    assert rows["copy.801"]["scope"] == "embedding"
    # a tuple result: the first is the row's dtype and shape, all are kept
    s = rows["slice-start.4"]
    assert s["opcode"] == "slice-start" and s["shape"] == (9, 4, 2, 8)
    assert ("bf16", (5, 4, 2, 8)) in s["shapes"]
    assert rows["slice-done.4"]["operands"] == ["slice-start.4"]


def test_loops_and_call_sites_around_an_instruction(rows):
    inner = rows["fusion.5"]
    assert inner["computation"] == "body"
    assert (inner["loops"], inner["calls"]) == ((5,), 5)
    assert inner["under"] == ("while",)
    assert rows["lt"]["loops"] == () and rows["lt"]["under"] == ("while",)
    assert rows["sort.1"]["under"] == ("conditional",)
    assert rows["sort.1"]["scope"] == "sampler"
    assert rows["while.1"]["opcode"] == "while"


def test_the_innermost_known_scope():
    assert H.scope_of("jit(step)/attention/kv_write/scatter") == "kv_write"
    assert H.scope_of("jit(f)/mlp/moe_route/dot_general") == "moe_route"
    assert H.scope_of("jit(step)/transpose(jvp(attention))/mul") == \
        "attention"
    assert H.scope_of("jit(step)/jit(silu)/neg") == ""
    assert H.scope_of("") == ""
    # a name that only holds a scope's letters is not that scope
    assert H.scope_of("jit(step)/mlp_helper/add") == ""
    assert H.scope_of("jit(step)/mlp/add", scopes=("attention",)) == ""


def test_a_fusion_takes_the_scope_most_of_its_body_carries(rows):
    # two of the three scoped instructions are in mlp; the ROOT and the
    # fusion's own op_name say attention; the compiler's convert, which
    # carries no name, does not vote
    f = rows["fusion.5"]
    assert f["op_name"].endswith("attention/add")
    assert f["scope"] == "mlp" and f["root"] == "add"
    # one each: the ROOT's
    assert rows["fusion.7"]["scope"] == "kv_write"
    # nothing in the body carries a name: the fusion's own (none here)
    assert rows["fusion.6"]["scope"] == "" and rows["fusion.6"][
        "root"] == "copy"


def test_what_nothing_scoped_was_fused_into_does_not_vote():
    # a residual add the source left outside every scope, fused into the
    # matmul before it: the matmul's scope
    body = [dict(H._parse(ln, H.SCOPES)) for ln in (
        '  %dot.1 = f32[8,16]{1,0} dot(%a, %b), metadata={op_name="jit(f)/mlp/dot_general"}',
        '  %add.8 = f32[8,16]{1,0} add(%dot.1, %c), metadata={op_name="jit(f)/add"}',
        '  ROOT %convert.3 = bf16[8,16]{1,0} convert(%add.8), metadata={op_name="jit(f)/convert_element_type"}')]
    assert H._fused_scope(body, "") == "mlp"
    assert H._fused_scope(body[1:], "lm_head") == "lm_head"


def test_an_asynchronous_pair_as_a_loaded_executable_prints_it(rows):
    start, done = rows["slice-start.5"], rows["slice-done.5"]
    assert (start["opcode"], start["root"], start["wraps"]) == (
        "async-start", "slice-start", "slice.3")
    assert (done["opcode"], done["root"]) == ("async-done", "slice-done")
    table = H.ProgramTable("step", H.instructions(TEXT), pool=POOL,
                           mesh_shape=MESH)
    assert table.get("slice-start.5")["role"] == "kv_pool"
    assert table.get("slice-done.5")["role"] == "kv_pool"
    # a wrapped collective's two halves run over its edge
    assert table.get("all-gather-start.9")["edge"] == "tp"
    assert table.get("all-gather-done.9")["edge"] == "tp"
    assert table.get("all-gather-done.9")["root"] == "all-gather-done"


def test_collectives_is_the_rows_that_are_collectives(rows):
    found = H.collectives(TEXT)
    assert [r["name"] for r in found] == [
        "all-gather.9", "psum.7", "all-gather-start.1"]
    named, gather = found[1:]
    # an all-reduce whatever the compiler named it, over the groups the
    # TEXT states
    assert named["family"] == "all-reduce" and named["opcode"] == \
        "all-reduce"
    assert named["groups"] == frozenset({(0, 2), (1, 3)})
    assert (named["dtypes"], named["bytes"], named["computation"],
            named["loops"], named["calls"]) == (
        ["f32"], 8 * 16 * 4, "body", (5,), 5)
    # an all-gather's bytes are what comes out
    assert gather["family"] == "all-gather"
    assert gather["bytes"] == 16 * 16 * 4
    assert gather["groups"] == frozenset({(0, 1), (2, 3)})
    assert "family" not in rows["all-gather-done.1"]


def test_roles_come_from_the_text_and_the_owners_shapes():
    table = H.ProgramTable("step", H.instructions(TEXT), pool=POOL,
                           mesh_shape=MESH)
    role = {r["name"]: r["role"] for r in table.rows}
    # the whole-pool copy of a parameter carries no scope, only its shape;
    # an asynchronous slice of the pool is the pool's through its operand
    for name in ("copy.800", "slice-start.4", "slice-done.4", "fusion.6"):
        assert role[name] == "kv_pool", name
    # a copy of anything else, and the pool's parameter itself, are not
    assert role["copy.801"] == "embedding"
    assert role["k_pages"] == "" and role["while.1"] == ""
    assert role["fusion.5"] == "mlp" and role["sort.1"] == "sampler"
    # copies are counted once a pair, at the -done
    pool_bytes = 9 * 4 * 2 * 8 * 2
    assert table.kv_pool_copy_bytes() == (
        pool_bytes + pool_bytes + (5 + 4) * 4 * 2 * 8 * 2)
    # an event's name as a trace prints it
    assert table.get("%copy.800") is table.get("copy.800")
    assert table.get("%copy.800 = bf16[9,4,2,8] copy(...)")["role"] == \
        "kv_pool"
    assert table.get("nothing.1") is None
    assert H.ProgramTable("step", H.instructions(TEXT)).get(
        "copy.800")["role"] == ""
    s = table.summary()
    assert s["instructions"] == len(table.rows)
    assert s["kv_pool_copy_bytes_per_launch"] == table.kv_pool_copy_bytes()
    assert s["scopes"] == {"embedding": 1, "kv_write": 1, "mlp": 1,
                           "sampler": 1, "transformer_layer": 1}
    json.dumps(s)
    assert "kv_pool" in table.table()


def test_a_collectives_edge_is_the_axis_its_groups_run_over():
    table = H.ProgramTable("step", H.instructions(TEXT), mesh_shape=MESH)
    edge = {r["name"]: r["edge"] for r in table.rows}
    assert edge["psum.7"] == "dp"
    assert edge["all-gather-start.1"] == "tp"
    assert edge["all-gather-done.1"] == "tp"        # its start's
    assert edge["copy.800"] == ""
    assert table.collectives_by_edge() == {
        "dp": {"all-reduce": {"calls": 5, "bytes": 5 * 8 * 16 * 4}},
        "tp": {"all-gather": {"calls": 6, "bytes": 6 * 16 * 16 * 4}}}
    assert H.edge_of(frozenset({(0, 1, 2, 3)}), MESH) == "dp+tp"
    assert H.edge_of(frozenset(), MESH) == "dp+tp"
    assert H.edge_of(frozenset({(0,), (1,), (2,), (3,)}), MESH) == ""
    # a ring's pairs lie inside its axis's groups
    ring = {"dp": 2, "tp": 4}
    assert H.edge_of(frozenset({(0, 1), (1, 2), (2, 3), (0, 3)}),
                     ring) == "tp"


# -- small programs compiled here ------------------------------------------

def _scanned(x, w):
    with jax.named_scope("attention"):
        y = jnp.tanh(x @ w)

    def body(c, _):
        with jax.named_scope("mlp"):
            return c * 2 + 1, None
    y, _ = jax.lax.scan(body, y, None, length=3)
    return y


def test_instructions_of_a_jitted_function_compiled_here():
    text = jax.jit(_scanned).lower(jnp.ones((8, 8)),
                                   jnp.ones((8, 8))).compile().as_text()
    rows = H.instructions(text)
    names = [r["name"] for r in rows]
    assert len(set(names)) == len(names)
    # a row's name is the text's own, without the %
    for r in rows:
        assert f"%{r['name']} = " in text or f" {r['name']} = " in text
    dots = [r for r in rows if r["opcode"] == "dot"]
    assert dots and all(r["scope"] == "attention" and r["loops"] == ()
                        for r in dots)
    assert dots[0]["op_name"].endswith("attention/dot_general")
    assert dots[0]["shape"] == (8, 8) and len(dots[0]["operands"]) == 2
    looped = [r for r in rows if r["scope"] == "mlp"]
    assert looped and all(r["loops"] == (3,) and "while" in r["under"]
                          for r in looped)
    assert H.collectives(text) == []


def test_a_psum_in_a_manual_region_is_an_all_reduce_over_its_axis():
    devices = np.array(jax.devices()[:8]).reshape(2, 4)
    mesh = Mesh(devices, ("dp", "tp"))

    def summed(x):
        over_dp = jax.lax.psum(x, "dp")
        return over_dp, jax.lax.psum(x * 2, "tp")

    fn = jax.jit(jax.shard_map(summed, mesh=mesh, in_specs=P("dp", "tp"),
                               out_specs=(P(None, "tp"), P("dp", None))))
    text = fn.lower(jnp.ones((8, 64))).compile().as_text()
    table = H.ProgramTable("summed", H.instructions(text),
                           mesh_shape=dict(mesh.shape))
    found = [r for r in table.rows if "family" in r]
    assert sorted(r["edge"] for r in found) == ["dp", "tp"]
    assert {r["family"] for r in found} == {"all-reduce"}
    # row for row what collectives() gives
    assert [r["name"] for r in H.collectives(text)] == [
        r["name"] for r in found]
    by_edge = table.collectives_by_edge()
    assert by_edge["dp"]["all-reduce"]["calls"] == 1
    assert by_edge["tp"]["all-reduce"]["calls"] == 1


# -- the engine's own programs ---------------------------------------------

class _Heard:
    """What jax says it lowered and compiled, from now on."""

    def __init__(self):
        self.events = []
        jax.monitoring.register_event_duration_secs_listener(self._hear)

    def _hear(self, event, secs, **kw):
        if self.events is not None and event.rsplit("/", 1)[-1] in (
                "jaxpr_to_mlir_module_duration", "backend_compile_duration"):
            self.events.append(event)

    def take(self):
        out, self.events = self.events, []
        return out


def _tiny(kind, **kw):
    from megatron_llm_tpu.serving import EngineConfig, InferenceEngine

    if kind == "sparse":
        from megatron_llm_tpu.models.olmoe import OlmoeModel, olmoe_config
        model = OlmoeModel(olmoe_config("tiny", use_flash_attn=False))
    else:
        from megatron_llm_tpu.models.llama import LlamaModel, llama_config
        model = LlamaModel(llama_config(
            "tiny", num_layers=2, seq_length=64, max_position_embeddings=64,
            padded_vocab_size=64, use_flash_attn=False))
    return InferenceEngine(model, model.init(jax.random.PRNGKey(0)),
                           EngineConfig(num_slots=4, block_size=16,
                                        max_model_len=64, prefill_chunk=16,
                                        default_deadline_secs=0.0, **kw))


def _serve(eng, n=3):
    from megatron_llm_tpu.serving import SamplingParams

    reqs = [eng.submit(list(range(1 + i, 22 + i)),
                       SamplingParams(max_new_tokens=4, temperature=0.0))
            for i in range(n)]
    while any(r.finish_reason is None for r in reqs):
        assert eng.step()


@pytest.mark.parametrize("kind", ["dense", "sparse"])
def test_the_engines_tables_cost_nothing_until_asked_for(kind):
    from megatron_llm_tpu.serving.loop_profiler import (
        LAUNCH_PROGRAMS, live_profilers)

    heard = _Heard()
    eng = _tiny(kind)
    eng.warmup()
    assert heard.take()                         # warm-up compiles
    launched = eng._decode_step
    _serve(eng)
    # no launch lowered or compiled anything, and no table was built
    assert heard.take() == []
    assert eng.stats()["programs"] is None
    assert eng.loop_profiler.programs == {}
    pool = eng.stats()["kv_pool_bytes"]
    assert pool == sum(a.nbytes for a in
                       jax.tree_util.tree_leaves(eng._st.pages))
    # asked for with no engine in hand, as the launch ring is reached:
    # jit's own cache has the executables that ran, so nothing compiles
    prof = next(p for p in live_profilers() if p is eng.loop_profiler)
    tables = prof.program_tables()
    assert heard.take() == []
    assert tables is eng.program_tables() is prof.programs
    assert set(tables) == {"engine_decode", "engine_prefill",
                           "engine_sample_first", "engine_cow_copy"}
    assert {p for ps in LAUNCH_PROGRAMS.values() for p in ps} >= {
        "engine_decode", "engine_prefill", "engine_sample_first"}
    # the launches are the parent's: the attribute is the jitted program
    assert eng._decode_step is launched is eng._jitted["engine_decode"]
    _serve(eng)
    assert heard.take() == []
    heard.events = None

    decode = tables["engine_decode"]
    copies = [r for r in decode.rows
              if r["role"] == "kv_pool" and r["root"] in H.COPIES]
    # the step owns the pool (it enters donated): no array of it is
    # copied, the scatter writes its rows in place
    array = {a.nbytes for a in jax.tree_util.tree_leaves(eng._st.pages)}
    assert copies == [] and len(array) == 1
    assert decode.kv_pool_copy_bytes() < array.pop()
    # the chunk is lent the pool: every array of it is copied whole
    chunk = tables["engine_prefill"]
    assert [r for r in chunk.rows
            if r["role"] == "kv_pool" and r["root"] in H.COPIES]
    assert chunk.kv_pool_copy_bytes() >= pool
    stats = eng.stats()["programs"]
    assert stats["engine_decode"] == decode.summary()
    assert stats["engine_decode"]["kv_pool_copy_bytes_per_launch"] == \
        decode.kv_pool_copy_bytes()
    scopes = set(stats["engine_decode"]["scopes"])
    assert {"sampler", "kv_write", "attention", "embedding",
            "lm_head"} <= scopes
    assert set(stats["engine_sample_first"]["scopes"]) == {"sampler"}
    assert stats["engine_prefill"]["kv_pool_copy_bytes_per_launch"] >= pool
    assert stats["engine_cow_copy"]["kv_pool_copy_bytes_per_launch"] >= pool
    if kind == "sparse":    # everything of its mlp is in an inner scope
        assert {"moe_route", "moe_dispatch", "moe_experts", "moe_combine",
                "qk_norm"} <= scopes
    else:
        assert "mlp" in scopes
    json.dumps(stats)


def test_an_engine_that_never_warmed_up_has_no_tables():
    eng = _tiny("dense")
    assert eng.program_tables() == {}
    assert eng.stats()["programs"] is None and eng.loop_profiler.programs == {}


@pytest.mark.parametrize("kw, programs", [
    (dict(speculative=True, draft_k=3), {"engine_verify"}),
    (dict(host_cache_bytes=1 << 20), {"engine_decode", "engine_fetch_block",
                                      "engine_host_load"}),
], ids=["speculative", "host_tier"])
def test_every_program_warm_up_compiled_has_its_table_with_no_compile(
        kw, programs):
    """The arguments ``program_tables`` lowers from are the launches' own:
    jit's cache answers for the verify step and the host tier's page
    programs too (a lowering here would mean a call site and
    ``_program_arguments`` have drifted apart)."""
    heard = _Heard()
    eng = _tiny("dense", **kw)
    try:
        eng.warmup()
        _serve(eng, n=2)
        heard.take()
        tables = eng.program_tables()
        assert heard.take() == []
    finally:
        heard.events = None
        eng.stop()
    assert set(tables) == programs | {"engine_prefill",
                                      "engine_sample_first",
                                      "engine_cow_copy"}
    step = tables["engine_verify" if "speculative" in kw
                  else "engine_decode"]
    # the step owns the pool and copies none of it; the chunk and the
    # page copy are lent theirs and copy it whole
    array = min(a.nbytes for a in jax.tree_util.tree_leaves(eng._st.pages))
    assert step.kv_pool_copy_bytes() < array
    assert not [r for r in step.rows
                if r["role"] == "kv_pool" and r["root"] in H.COPIES]
    for lent in ("engine_prefill", "engine_cow_copy"):
        assert tables[lent].kv_pool_copy_bytes() >= eng.kv_pool_bytes, lent
    assert "sampler" in step.summary()["scopes"]


def test_a_registered_program_is_built_when_first_read():
    from megatron_llm_tpu.serving import loop_profiler

    built = []

    def build():
        built.append(1)
        return H.ProgramTable("step", H.instructions(TEXT), mesh_shape=MESH)

    loop_profiler.register_program("made_up_step", build)
    try:
        assert built == []
        table = loop_profiler.live_programs()["made_up_step"]
        assert loop_profiler.live_programs()["made_up_step"] is table
        assert built == [1] and table.get("psum.7")["edge"] == "dp"
        loop_profiler.register_program("none_yet", lambda: None)
        assert "none_yet" not in loop_profiler.live_programs()
    finally:
        with loop_profiler._LIVE_LOCK:
            for name in ("made_up_step", "none_yet"):
                loop_profiler._PROGRAM_SOURCES.pop(name, None)
                loop_profiler._PROGRAM_TABLES.pop(name, None)
