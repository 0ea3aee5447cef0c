"""Ahead-of-time compiles for a described TPU v5e chip.

Nothing here runs on a chip: the TPU compiler, installed alongside JAX,
compiles each kernel (and the whole resident serve step) for a v5e device
that is described, not attached. That catches what interpret mode cannot
(Mosaic lowering gaps, block shapes the (8, 128) tiling refuses) at no chip
time. The widths are the served models': SHD (700 -> 64 -> 20, window 32,
64 cohort slots), ECG (4 -> 64 recurrent -> 6, T = 1301), the plastic
window (64 -> 32 -> 4), and a 10^5-neuron banded topology at the default
gather blocks.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and a worker that described it at import
would keep every other worker from collecting this file.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import registry

f32 = jnp.float32

# SHD serving: window 32 x 64 cohort slots, 700 inputs, 64 hidden, 20 out
SHD_T, SHD_B, SHD_IN, SHD_H, SHD_OUT, SHD_K = 32, 64, 700, 64, 20, 4
# ECG: the QTDB sequence length, a small batch, 64 recurrent ALIF neurons
ECG_T, ECG_B, ECG_H, ECG_OUT = 1301, 8, 64, 6
# plastic window: 64 -> 32 pair-STDP edge (K = 2 term planes), batch 8
PL_T, PL_B, PL_IN, PL_H = 32, 8, 64, 32
# 10^5-neuron banded topology (band 32) at the default 128x128 gather blocks
TOPO_N, TOPO_M, TOPO_BLK = 100_000, 32, 128


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _steer_to_chip(mp):
    """Steer dispatch as it runs on the chip: compiled Pallas kernels, the
    spec-default blocks (the checked-in tuning cache holds CPU entries
    only, which the chip never sees)."""
    from repro.kernels import tuning
    mp.setenv("REPRO_KERNEL_IMPL", "pallas")
    mp.setenv("REPRO_PALLAS_INTERPRET", "0")
    mp.setattr(tuning, "lookup_tuned", lambda *a, **k: None)


@pytest.fixture
def chip_dispatch(monkeypatch):
    _steer_to_chip(monkeypatch)


def _compile(fn, shapes, one_chip):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text, "no Pallas kernel in the program"
    return compiled


# (family, width tag, operand shapes, static kwargs for spec.pallas)
KERNEL_CASES = [
    ("spikemm", "shd_hidden",
     [((SHD_T * SHD_B, SHD_IN), f32), ((SHD_IN, SHD_H), f32)], {}),
    ("spikemm", "shd_branches",
     [((SHD_T * SHD_B, SHD_IN), f32), ((SHD_IN, SHD_K * SHD_H), f32)], {}),
    ("spikemm", "shd_readout",
     [((SHD_T * SHD_B, SHD_H), f32), ((SHD_H, SHD_OUT), f32)], {}),
    ("lif", "shd",
     [((SHD_T, SHD_B, SHD_H), f32), ((SHD_H,), f32), ((SHD_B, SHD_H), f32)],
     {"v_th": 1.0, "reset": "zero"}),
    ("alif", "shd",
     [((SHD_T, SHD_B, SHD_H), f32), ((SHD_H,), f32), ((SHD_H,), f32),
      ((SHD_B, SHD_H), f32), ((SHD_B, SHD_H), f32)], {}),
    ("linrec", "shd_readout",
     [((SHD_T, SHD_B, SHD_OUT), f32)] * 2 + [((SHD_B, SHD_OUT), f32)], {}),
    ("linrec", "shd_branches",
     [((SHD_T, SHD_B * SHD_K, SHD_H), f32)] * 2
     + [((SHD_B * SHD_K, SHD_H), f32)], {}),
    ("lifrec", "ecg",
     [((ECG_T, ECG_B, ECG_H), f32), ((ECG_H, ECG_H), f32), ((ECG_H,), f32),
      ((ECG_B, ECG_H), f32), ((ECG_B, ECG_H), f32)], {}),
    ("alifrec", "ecg",
     [((ECG_T, ECG_B, ECG_H), f32), ((ECG_H, ECG_H), f32), ((ECG_H,), f32),
      ((ECG_H,), f32), ((ECG_B, ECG_H), f32), ((ECG_B, ECG_H), f32),
      ((ECG_B, ECG_H), f32)], {}),
    ("linrec", "ecg_readout",
     [((ECG_T, ECG_B, ECG_OUT), f32)] * 2 + [((ECG_B, ECG_OUT), f32)], {}),
    ("stdp_seq", "plastic",
     [((2, PL_T * PL_B, PL_IN), f32), ((2, PL_T * PL_B, PL_H), f32),
      ((PL_IN, PL_H), f32)],
     {"amps": (0.01, -0.012), "w_min": -1.0, "w_max": 1.0, "batch": PL_B}),
    ("linrec", "plastic_traces",
     [((PL_T, PL_B, PL_IN), f32)] * 2 + [((PL_B, PL_IN), f32)], {}),
]


@pytest.mark.parametrize(
    "family,width,shapes,static", KERNEL_CASES,
    ids=[f"{c[0]}-{c[1]}" for c in KERNEL_CASES])
def test_kernel_compiles_for_v5e(family, width, shapes, static, one_chip):
    spec = registry.get(family)
    dims = spec.dims_of(*[jax.ShapeDtypeStruct(s, d) for s, d in shapes])
    blocks = spec.resolve_blocks(dims, use_cache=False)
    _compile(lambda *a: spec.pallas(*a, blocks=blocks, interpret=False,
                                    **static), shapes, one_chip)


def test_spikemm_sparse_channel_compiles_for_v5e(one_chip, chip_dispatch,
                                                 monkeypatch):
    """The block-sparse channel under jit (capacity-padded entry list)."""
    from repro.kernels.spikemm.ops import spikemm
    monkeypatch.setenv("REPRO_SPIKEMM_SPARSE", "always")
    _compile(lambda s, w: spikemm(s, w),
             [((SHD_T * SHD_B, SHD_IN), f32), ((SHD_IN, SHD_H), f32)],
             one_chip)


def test_spikemm_gather_compiles_for_v5e_at_default_blocks(one_chip):
    """The 10^5-neuron banded topology's gather kernel: the tables are
    operands, so their shapes alone (E occupied 128x128 blocks) suffice."""
    from repro.kernels.spikemm import gather
    nb = -(-TOPO_N // TOPO_BLK)
    E = 3 * nb - 2                   # band 32 < 128: own block +- one
    blocks = registry.get("spikemm_gather").resolve_blocks(
        {"M": TOPO_M}, use_cache=False)
    i32 = jnp.int32
    _compile(lambda jj, kk, act, s, w: gather._gather_pallas(
                 jj, kk, act, s, w, bm=blocks["bm"], bk=TOPO_BLK,
                 bn=TOPO_BLK, jb=nb),
             [((E,), i32), ((E,), i32), ((E,), i32),
              ((TOPO_M, nb * TOPO_BLK), f32), ((E, TOPO_BLK, TOPO_BLK), f32)],
             one_chip)


@pytest.mark.parametrize("dendritic", [False, True],
                         ids=["lif", "dhlif"])
def test_resident_serve_step_compiles_for_v5e(dendritic, one_chip,
                                              chip_dispatch):
    """The whole jitted cohort step the SHD server runs every window."""
    from repro.core import plan as plan_mod
    from repro.core.snn_layers import make_dhsnn_shd
    from repro.serve import EngineConfig, make_engine
    from repro.serve.engine import _resident_step

    nodes, params = make_dhsnn_shd(jax.random.PRNGKey(0), n_in=SHD_IN,
                                   n_hidden=SHD_H, n_out=SHD_OUT,
                                   dendritic=dendritic)
    eng = make_engine(nodes, params,
                      EngineConfig(window=SHD_T, capacity=SHD_B))
    assert not [s for s in eng.plan.segments
                if s.kind == plan_mod.FALLBACK], eng.plan.describe()
    step = _resident_step(eng.nodes, eng.plan, False, None)

    def abstract(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(np.shape(a), a.dtype,
                                           sharding=one_chip), tree)

    pool = abstract(eng.pool.leaves)
    rows = jax.ShapeDtypeStruct((SHD_B,), jnp.int32, sharding=one_chip)
    fresh = jax.ShapeDtypeStruct((SHD_B,), jnp.bool_, sharding=one_chip)
    x = jax.ShapeDtypeStruct((SHD_T, SHD_B, SHD_IN), f32, sharding=one_chip)
    compiled = step.lower(abstract(params), pool, rows, fresh, x).compile()
    text = compiled.as_text()
    # the flat step carries no synapse entry and no per-slot operand: the
    # params, one array per pool leaf, then rows, fresh and the input,
    # however many slots the cohort has
    assert not [k for nd in eng.pool.leaves.values() for k in nd
                if k.startswith("syn:")]
    n_args = (len(jax.tree_util.tree_leaves(params))
              + len(jax.tree_util.tree_leaves(eng.pool.leaves)) + 3)
    entry = re.search(r"^ENTRY .*?^\}", text, re.M | re.S).group(0)
    assert len(re.findall(r"= \S+ parameter\(\d+\)", entry)) == n_args
    assert n_args <= 16, n_args
    assert text.count("tpu_custom_call") >= 3, "spikemm, lif, linrec kernels"
    # each kernel's instruction carries its wrapper's name, which is how a
    # profiler trace names it
    for kernel in ("spikemm_pallas", "lif_pallas", "linrec_pallas"):
        assert re.search(rf"%{kernel}\.\d+ = .*custom-call\(", text), kernel
    # the stages of the step tag their ops' metadata
    scopes = set(re.findall(r'op_name="jit\(step\)/(\w+)/', text))
    assert {"integ", "fire", "pack", "unpack"} <= scopes, scopes


# the Potjans-Diesmann circuit's stream chunk: 64 ring slots, 200 steps
MC_R, MC_T = 64, 200


@pytest.fixture(scope="module")
def microcircuit_chunk(one_chip):
    """The jitted 200-step stream chunk of the Potjans-Diesmann circuit at
    its published sizes (77,169 neurons, 2.99e8 synapses in a 2.7 GB
    table of windows), compiled once for the module: (compiled, the HLO
    text, its computations by name)."""
    with pytest.MonkeyPatch.context() as mp:
        _steer_to_chip(mp)
        compiled = _compile_microcircuit_chunk(one_chip)
    text = compiled.as_text()
    comps = {m.group(1): m.group(2) for m in re.finditer(
        r"^(?:ENTRY )?%([\w.-]+) [^\n]*\{$(.*?)^\}$", text, re.M | re.S)}
    return compiled, text, comps


def _compile_microcircuit_chunk(one_chip):
    from repro.core import events, microcircuit as mc, plan as plan_mod
    from repro.core.neuron import IafPscExp
    from repro.core.snn_layers import ff_integrate
    from repro.core.topology import encode_fanout
    from repro.kernels.event.ops import EventTables, GroupTables

    # 298,880,968 synapses in rows of 512-entry windows: the builder's
    # table holds ceil(synapses / 512) + N rows (~623,000 of them used)
    n, R, T, W, syn = mc.N_FULL, MC_R, MC_T, 512, 298_880_968
    neuron = IafPscExp(bg_weight=87.81)
    nodes = []
    for q, name in enumerate(mc.POPULATIONS):
        conns = tuple(
            events.Connection("self" if p == q else mc.POPULATIONS[p],
                              topology=encode_fanout(
                                  np.array([0]), np.array([0]),
                                  np.array([1.0], np.float32),
                                  np.array([R - 1]), n[p], n[q]))
            for p in range(8) if mc.CONN_PROB[q][p] > 0)
        nodes.append(events.LayerNode(name, neuron, ff_integrate, conns,
                                      n[q]))
    plan = plan_mod.compile_program(nodes)
    assert [s.kind for s in plan.segments] == [plan_mod.EVENT]

    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    N = sum(n)
    params = {name: {"neuron": {"bg_cdf": S((13, n[q]), f32),
                                "bg_key": S((2,), jnp.uint32)}}
              for q, name in enumerate(mc.POPULATIONS)}
    i32 = jnp.int32
    tables = GroupTables(EventTables(
        S((N,), i32), S((N,), i32), S((N,), i32),
        S((-(-syn // W) + N, 2 * W), i32)), 17, W)
    state = {name: {"v": S((1, n[q]), f32), "i": S((1, n[q]), f32),
                    "ref": S((1, n[q]), i32), "out": S((1, n[q]), f32),
                    "t": S((), i32), "arr": S((R, 1, n[q]), f32)}
             for q, name in enumerate(mc.POPULATIONS)}
    step = plan_mod.stream_step(nodes, plan, spikes="list")
    return step.lower(params, {0: tables}, state,
                      S((T, 1, 0), f32)).compile()


def _called(comps, root):
    """`root` and every computation it calls, directly or not."""
    seen, todo = set(), [root]
    while todo:
        c = todo.pop()
        if c not in seen:
            seen.add(c)
            todo += [k for k in re.findall(r"%([\w.-]+)", comps[c])
                     if k in comps]
    return seen


def _pass_loop(text):
    """(the pass loop's instruction, its body): `push_roofline.sim` finds
    the push's pass loop in the trace as the innermost loop around the
    one operation that writes the flat ring."""
    from repro.core import microcircuit as mc
    ring = re.compile(
        rf"^\s*%[\w.-]+ = f32\[{MC_R * sum(mc.N_FULL)}\]\S* (fusion|scatter)\(",
        re.M)
    hits = ring.findall(text)
    assert len(hits) == 1, hits
    at = ring.search(text).start()
    head = re.findall(r"^(?:ENTRY )?%([\w.-]+) .*\{$", text[:at], re.M)[-1]
    loops = re.findall(
        rf"^\s*(%while[.\d]*) = .* while\(.*body=%{re.escape(head)}\b",
        text, re.M)
    assert len(loops) == 1, f"the scatter's computation {head} is no loop body"
    return loops[0], head


def test_microcircuit_chunk_compiles_for_v5e(microcircuit_chunk):
    """The whole EVENT segment, with the push's scatter into the flat
    ring, and the pass's windows fetched without a loop."""
    compiled, text, comps = microcircuit_chunk
    _, head = _pass_loop(text)
    # the pass fetches its windows with one gather: no loop runs inside
    # the pass loop's body, nor in any computation it calls
    for c in _called(comps, head):
        assert not re.search(r" while\(", comps[c]), f"a loop in {c}"
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes < 4e9


def test_microcircuit_scan_keeps_the_ring_flat(microcircuit_chunk):
    """The 200-step scan carries the arrival ring flat: a step runs the
    push's pass loop and no other loop, and neither relays the ring into
    its 3-D (R, B, N) layout nor builds a whole ring of zeros. Those move
    the whole 19.8 MB ring every step and do no arithmetic; the one
    relayout each way belongs at the chunk's entry and exit."""
    from repro.core import microcircuit as mc
    _, text, comps = microcircuit_chunk
    N = sum(mc.N_FULL)
    pass_loop, _ = _pass_loop(text)
    scans = [c for c, body in comps.items()
             if re.search(rf"^\s*{re.escape(pass_loop)} = ", body, re.M)]
    assert len(scans) == 1, scans
    scan = scans[0]
    assert re.search(rf"^\s*%while[.\d]* = .* while\(.*body=%{re.escape(scan)}\b",
                     text, re.M), f"{scan}, which runs the pass loop, is no loop body"
    step = "\n".join(comps[c] for c in _called(comps, scan))
    loops = re.findall(r"^\s*(%[\w.-]+) = .* while\(", step, re.M)
    assert loops == [pass_loop], loops
    shaped = re.findall(
        rf"^\s*%[\w.-]+ = f32\[(?:{MC_R},1,{N}|1,{MC_R},{N})\].*$", step, re.M)
    assert not shaped, shaped[:3]
    zeros = re.findall(
        rf"^\s*%[\w.-]+ = f32\[(?:{MC_R * N}|{MC_R},1,{N}|1,{MC_R},{N})\]"
        r"\S* broadcast\(.*$", step, re.M)
    assert not zeros, zeros[:3]
