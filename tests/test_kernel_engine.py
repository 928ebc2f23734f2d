"""kernels/engine.py: which engine a kernel-backed op runs is decided in one
module.  (a) `tiles_or_none` and `site` over the door x what the program is
for x the mesh, `shard_over_mesh` beside them, and the door's one
vocabulary; (b) no file of paddle_tpu/kernels/ imports paddle_tpu.ops or a
sibling's private name, and the budget and the `vmem_limit_bytes` rule are
written in engine.py alone; (c) the op files that choose an engine ask
kernels.engine, and none asks kernels/flash_attention.py."""

import ast
import os
import sys
import types

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

import paddle_tpu as fluid
from paddle_tpu.kernels import engine

KERNELS = os.path.join(REPO, "paddle_tpu", "kernels")
OPS = os.path.join(REPO, "paddle_tpu", "ops")


def _sources(directory):
    return sorted(f for f in os.listdir(directory) if f.endswith(".py"))


# ---------------------------------------------------------------------------
# (a) the engine of a site
# ---------------------------------------------------------------------------
# whether the door lets the kernels run, by what the program is for
ASKS_FOR_KERNELS = {"auto": {"tpu": True, "cpu": False},
                    "pallas": {"tpu": True, "cpu": True},
                    "interpret": {"tpu": True, "cpu": True},
                    "jax": {"tpu": False, "cpu": False}}


@pytest.mark.parametrize("devices", (None, 1, 4))
@pytest.mark.parametrize("platform", ("cpu", "tpu"))
@pytest.mark.parametrize("force", engine.DOOR)
def test_tiles_or_none(force, platform, devices):
    """The plan's tiles where the door and the platform allow the kernels
    and the mesh rule does (one device); None, and the planner not asked,
    anywhere else."""
    mesh = devices and types.SimpleNamespace(num_devices=devices)
    asked = []

    def plan():
        asked.append(True)
        return "tiles"

    with fluid.flags.tpu_trace_scope(platform == "tpu"):
        got = engine.tiles_or_none(force, mesh, plan)
        assert engine.wants_kernels(force) == ASKS_FOR_KERNELS[force][platform]
    runs = ASKS_FOR_KERNELS[force][platform] and devices != 4
    assert got == ("tiles" if runs else None)
    assert bool(asked) == runs
    assert engine.several_devices(mesh) == (devices == 4)


def test_a_plan_that_does_not_tile_is_the_jax_numpy_form():
    assert engine.tiles_or_none("interpret", None, lambda: None) is None


@pytest.mark.parametrize("devices", (None, 1, 4))
@pytest.mark.parametrize("platform", ("cpu", "tpu"))
def test_flash_attention_shards_itself_for_a_tpu_on_several_devices(
        platform, devices):
    """The other behaviour of the mesh rule: the call in a shard_map where
    the program is for a TPU on several devices, itself anywhere else."""
    import jax

    from paddle_tpu.parallel import make_mesh

    mesh = devices and make_mesh({"dp": devices},
                                 devices=jax.devices()[:devices])

    def attend(q, k, v):
        return q

    with fluid.flags.tpu_trace_scope(platform == "tpu"):
        got = engine.shard_over_mesh(attend, mesh, 8, False)
    assert (got is attend) == (platform == "cpu" or devices != 4)


def test_streams_of_mixed_or_odd_dtypes_are_not_one_dtype():
    import jax.numpy as jnp

    def one(*dtypes):
        return engine.one_dtype(*(types.SimpleNamespace(dtype=jnp.dtype(d))
                                  for d in dtypes))

    assert one("bfloat16", "bfloat16") and one("float32")
    assert not one("bfloat16", "float32") and not one("float16")


@pytest.mark.parametrize("word", ("xla", "Pallas", None, ""))
def test_the_door_has_one_vocabulary(word):
    """auto | pallas | interpret | jax and no other spelling, in every
    kernel file's entry."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.kernels import dropout_mask, sparse_attention

    assert engine.DOOR == ("auto", "pallas", "interpret", "jax")
    with pytest.raises(ValueError, match="none of auto"):
        engine.wants_kernels(word)
    with pytest.raises(ValueError):
        dropout_mask.draw(jax.random.PRNGKey(0), (64, 128), 0.1, force=word)
    x = jnp.zeros((1, 1, 8, 8))
    with pytest.raises(ValueError):
        sparse_attention.sparse_attention(
            x, x, x, x, x[0], x[0], topk=4, scale=1.0, q_chunk=8,
            kv_chunk=8, force=word)
    with pytest.raises(TypeError):
        sparse_attention.sparse_attention(
            x, x, x, x, x[0], x[0], topk=4, scale=1.0, engine="xla")


def test_a_site_runs_one_engine_and_its_span_says_which():
    from paddle_tpu import observability

    tiles = types.SimpleNamespace(rows=256, fwd_vmem_bytes=1000, more=7)
    fields, ran = ("rows", "fwd_vmem_bytes"), []

    def site(what, plan, mesh=None):
        return engine.site(
            "x.lower", fields, mesh, plan,
            lambda tiles, interpret: ran.append((tiles.rows, interpret)),
            lambda: ran.append("form"), force="interpret", what=what)

    fluid.flags._VALUES["FLAGS_observability"] = True
    try:
        observability.reset()
        site("a", lambda: tiles)
        site("b", lambda: None)
        site("c", lambda: tiles, types.SimpleNamespace(num_devices=4))
        spans = [dict(s.args) for s in observability.default_tracer().spans()
                 if s.name == "x.lower"]
    finally:
        fluid.flags._VALUES["FLAGS_observability"] = False
        observability.reset()
    assert ran == [(256, True), "form", "form"]
    assert spans == [
        dict(what="a", engine="pallas", rows=256, fwd_vmem_bytes=1000),
        dict(what="b", engine="xla", rows=0, fwd_vmem_bytes=0),
        dict(what="c", engine="xla", rows=0, fwd_vmem_bytes=0)]


# ---------------------------------------------------------------------------
# (b) kernels/ imports nothing above itself and no sibling's private name
# ---------------------------------------------------------------------------
def _imports(path):
    """[(level, module or "", name)] of every import of a file, at module
    level or inside a function."""
    with open(path) as f:
        tree = ast.parse(f.read())
    found = []
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            found += [(0, alias.name, "") for alias in node.names]
        elif isinstance(node, ast.ImportFrom):
            found += [(node.level, node.module or "", alias.name)
                      for alias in node.names]
    return found


@pytest.mark.parametrize("name", _sources(KERNELS))
def test_a_kernel_file_imports_no_op_and_no_siblings_private_name(name):
    siblings = {f[:-3] for f in _sources(KERNELS)}
    for level, module, what in _imports(os.path.join(KERNELS, name)):
        path = module.split(".")
        # `from ..ops import x`, `from .. import ops`, `import paddle_tpu.ops`
        above = (level == 2 and (path[0] == "ops" or (
            not module and what == "ops"))) or (
            level == 0 and path[:2] == ["paddle_tpu", "ops"])
        assert not above, f"{name} imports ops: {module} {what}"
        if level == 1 and (path[0] in siblings or not module):
            assert not what.startswith("_"), \
                f"{name} imports {what} from its sibling {module}"


def test_the_budget_and_the_vmem_limit_rule_are_written_once():
    """`(3 * V5E_VMEM_BYTES) // 4` and `max(V5E_VMEM_BYTES, 2 * need)`: no
    kernel file but engine.py names the chip's VMEM."""
    named = []
    for name in _sources(KERNELS):
        with open(os.path.join(KERNELS, name)) as f:
            tree = ast.parse(f.read())
        if any(isinstance(n, ast.Name) and n.id == "V5E_VMEM_BYTES"
               for n in ast.walk(tree)):
            named.append(name)
    assert named == ["engine.py"]
    from paddle_tpu.analysis.pallas import V5E_VMEM_BYTES

    assert engine.PLAN_VMEM_BUDGET == (3 * V5E_VMEM_BYTES) // 4
    small, large = (engine.compiler_params(("parallel",), need)
                    for need in (1, V5E_VMEM_BYTES))
    assert small.vmem_limit_bytes == V5E_VMEM_BYTES
    assert large.vmem_limit_bytes == 2 * V5E_VMEM_BYTES


# ---------------------------------------------------------------------------
# (c) ops/ asks kernels.engine
# ---------------------------------------------------------------------------
# what an op file may take from kernels/flash_attention.py: attention's own
ATTENTIONS_OWN = {"_visible_pairs", "heads_first_shapes", "kept",
                  "kept_bytes", "takes_heads_last", "flash_attention"}
# the op files that choose an engine, and what of kernels/engine.py they ask
ASKS = {"attention_ops.py": {"shard_over_mesh", "wants_kernels", "site",
                             "use_pallas"},
        "linear_attention_ops.py": {"site", "one_dtype"},
        "hyper_connection_ops.py": {"site", "one_dtype"},
        "state_space_ops.py": {"site"},
        "moe_ops.py": {"use_pallas"}}


@pytest.mark.parametrize("name", _sources(OPS))
def test_an_op_file_takes_the_platform_the_door_and_the_mesh_rule_from_engine(
        name):
    path = os.path.join(OPS, name)
    for level, module, what in _imports(path):
        if module.endswith("kernels.flash_attention"):
            assert what in ATTENTIONS_OWN, f"{name} takes {what} from flash"
    with open(path) as f:
        tree = ast.parse(f.read())
    asked = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Attribute) and isinstance(
                node.value, ast.Name) and node.value.id == "engine":
            asked.add(node.attr)
        if isinstance(node, ast.ImportFrom) and (
                node.module or "").endswith("kernels.engine"):
            asked |= {alias.name for alias in node.names}
    assert asked == ASKS.get(name, set())
