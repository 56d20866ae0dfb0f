"""The seam around the KV cache: ``models/kv_cache.py`` is the one module
that knows what a cache is. The arrows between the modules, read off
their syntax trees; the prefix pool's storage against the engine's cache,
both from the one allocator; the engines' one geometry check; and the
views an admission writes through."""
import ast
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.distributed.mesh import init_mesh, set_mesh
from paddle_tpu.models import kv_cache
from paddle_tpu.models.generation import GenerationEngine
from paddle_tpu.models.gpt import GPTForCausalLM, gpt_tiny
from paddle_tpu.models.ouro import OuroForCausalLM, ouro_tiny
from paddle_tpu.models.speculative import SpeculativeEngine, build_draft_model
from paddle_tpu.serving.engine import ContinuousBatchingEngine
from paddle_tpu.serving.prefix_cache import BlockPool
from paddle_tpu.serving.scheduler import Request

PKG = pathlib.Path(pt.__file__).parent
#: the modules that hold, allocate or copy caches besides the owner
CLIENTS = ("models/generation.py", "models/speculative.py",
           "serving/engine.py", "serving/prefix_cache.py")


def _tree(rel):
    return ast.parse((PKG / rel).read_text())


def _imports(tree):
    """``(module, names)`` of every import statement, the relative dots
    dropped (``..models.kv_cache`` reads ``models.kv_cache``)."""
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom):
            yield node.module or "", [a.name for a in node.names]
        elif isinstance(node, ast.Import):
            for a in node.names:
                yield a.name, []


def _modules(*dirs):
    for d in dirs:
        for path in sorted((PKG / d).rglob("*.py")):
            yield path.relative_to(PKG).as_posix()


# ------------------------------------------------------------- the arrows
def test_the_owner_imports_none_of_its_clients():
    for module, names in _imports(_tree("models/kv_cache.py")):
        parts = set(module.split(".")) | set(names)
        assert not parts & {"lm_utils", "generation", "speculative", "lora",
                            "serving"}, (module, names)


@pytest.mark.parametrize("kernel", ["cache_write", "cache_read"])
def test_the_kernel_has_one_importer(kernel):
    """Outside its own package, which lists its modules."""
    importers = [
        rel for rel in _modules(".")
        if rel != "kernels/__init__.py" and any(
            module.endswith(kernel) or kernel in names
            for module, names in _imports(_tree(rel)))]
    assert importers == ["models/kv_cache.py"]


@pytest.mark.parametrize("rel", [
    rel for rel in _modules("models", "serving")
    if rel != "models/kv_cache.py"])
def test_only_the_owner_knows_the_quantized_entry(rel):
    """No other module of the models or of serving asks whether an entry
    is quantized, or compares anything with ``"int8"``."""
    for node in ast.walk(_tree(rel)):
        if isinstance(node, (ast.Name, ast.Attribute, ast.alias)):
            name = getattr(node, "id", None) or getattr(
                node, "attr", None) or getattr(node, "name", None)
            assert name != "is_quantized_kv", rel
        if isinstance(node, ast.Compare):
            for side in [node.left] + node.comparators:
                assert not (isinstance(side, ast.Constant)
                            and side.value == "int8"), (rel, node.lineno)


def test_no_private_name_crosses_a_module():
    """No ``from … import _name`` out of the modules that hold cache
    code, anywhere in the package."""
    holders = ("kv_cache", "generation", "lm_utils", "cache_write",
               "cache_read", "prefix_cache", "speculative")
    for rel in _modules("."):
        for module, names in _imports(_tree(rel)):
            if module.split(".")[-1] in holders:
                private = [n for n in names if n.startswith("_")]
                assert not private, (rel, module, private)


@pytest.mark.parametrize("rel", CLIENTS + ("models/lm_utils.py",))
def test_clients_take_cache_names_from_the_owner_alone(rel):
    """Whatever a client imports with ``cache`` or ``kv`` in its name
    (or ``CacheRow``) comes from ``models.kv_cache``; the models' one
    call, ``attend_with_cache`` (``attend_with_latent_cache`` for a
    latent entry), is ``lm_utils``'s own."""
    for module, names in _imports(_tree(rel)):
        cache_names = [n for n in names
                       if "cache" in n.lower() or "kv" in n.lower()]
        if module.split(".")[-1] in ("compile_cache", "framework"):
            continue                  # the compile cache is another cache
        if cache_names:
            assert module.split(".")[-1] == "kv_cache", (rel, module,
                                                         cache_names)


def test_the_old_homes_define_no_cache_function():
    for rel, allowed in (("models/generation.py", set()),
                         ("models/lm_utils.py", {"attend_with_cache",
                                                 "attend_with_latent_cache",
                                                 "cached_lm_forward"})):
        defined = {node.name for node in _tree(rel).body
                   if isinstance(node, (ast.FunctionDef, ast.ClassDef))}
        assert {n for n in defined if "cache" in n.lower()} == allowed, rel
    text = "".join((PKG / rel).read_text() for rel in _modules("."))
    assert "_constrain_cache" not in text
    assert "slice_cache_rows" not in text
    pool = (PKG / "serving/prefix_cache.py").read_text()
    assert "jnp.zeros" not in pool


def test_the_package_reexports_come_from_the_owner():
    from paddle_tpu import models

    for name in ("init_cache", "cache_nbytes", "scatter_cache_rows"):
        assert getattr(models, name) is getattr(kv_cache, name)


# ------------------------------------------------- pool against the cache
def _gpt(**kw):
    return GPTForCausalLM(gpt_tiny(hidden_dropout_prob=0.0,
                                   attention_dropout_prob=0.0,
                                   use_flash_attention=False, **kw))


def _looped(**kw):
    return OuroForCausalLM(ouro_tiny(**kw))


#: (block_bytes, num_blocks at a 1 MiB budget, at 300 000 bytes) of a
#: pool of 16-token blocks: what PR 29's arithmetic gave (its
#: ``head_dim + 4`` for an int8 entry, ``2 * entries * tokens * heads``
#: around it), from a run of that tree
PARENT_POOL = {
    ("gpt", "bfloat16", None): (16384, 65, 19),
    ("gpt", "float32", None): (32768, 33, 10),
    ("gpt", "float32", "int8"): (9216, 114, 33),
    ("gpt", "bfloat16", "int8"): (9216, 114, 33),
    ("looped", "bfloat16", None): (24576, 43, 13),
    ("looped", "float32", None): (49152, 22, 7),
    ("looped", "float32", "int8"): (15360, 69, 20),
    ("looped", "bfloat16", "int8"): (15360, 69, 20),
}


@pytest.mark.parametrize("which,dtype,kv_dtype", sorted(
    PARENT_POOL, key=str))
def test_pool_storage_is_the_caches_own(which, dtype, kv_dtype):
    model = {"gpt": _gpt, "looped": _looped}[which](dtype=dtype)
    block_bytes, blocks_1m, blocks_300k = PARENT_POOL[which, dtype, kv_dtype]
    pool = BlockPool(model, block_tokens=16, max_bytes=1 << 20,
                     max_length=64, kv_dtype=kv_dtype)
    assert (pool.block_bytes, pool.num_blocks) == (block_bytes, blocks_1m)
    small = BlockPool(model, block_tokens=16, max_bytes=300_000,
                      max_length=64, kv_dtype=kv_dtype)
    assert (small.block_bytes, small.num_blocks) == (block_bytes,
                                                     blocks_300k)
    cache = kv_cache.init_cache(model, 3, 64, kv_dtype=kv_dtype)
    assert (jax.tree.structure(pool.tensors)
            == jax.tree.structure(cache))
    for block, leaf in zip(jax.tree.leaves(pool.tensors),
                           jax.tree.leaves(cache)):
        assert block.dtype == leaf.dtype
        assert block.shape[0] == pool.num_blocks
        # rows lead, then the stack; a block is 16 of the 64 positions
        assert block.shape[1:-3] == leaf.shape[1:-3]
        assert block.shape[-3] == 16 and leaf.shape[-3] == 64
        assert block.shape[-2:] == leaf.shape[-2:]
    # a block's bytes are its share of the pool's, and a token's bytes
    # are a row's share of the cache's: one statement of the format
    assert (kv_cache.cache_nbytes(pool.tensors)
            == pool.num_blocks * pool.block_bytes)
    assert (kv_cache.cache_token_nbytes(model.cache_spec(), kv_dtype=kv_dtype)
            == kv_cache.cache_nbytes(cache) // (3 * 64))


# ----------------------------------------------------- the one geometry
def _generation(model, **kw):
    return GenerationEngine(model, **kw)


def _serving(model, **kw):
    return ContinuousBatchingEngine(model, slots=2, **kw)


def _speculative(model, **kw):
    return SpeculativeEngine(model, build_draft_model(model, 1), k=2, **kw)


def _short_draft(model, **kw):
    draft = _gpt(max_position_embeddings=128, num_layers=1)
    return SpeculativeEngine(model, draft, k=2, **kw)


@pytest.mark.parametrize("engine,who,length", [
    (_generation, "model's", 257), (_serving, "model's", 257),
    (_speculative, "target's", 257), (_short_draft, "DRAFT's", 200)])
def test_engines_refuse_a_cache_past_the_position_table(engine, who, length):
    model = _gpt()                              # 256 positions
    with pytest.raises(ValueError, match=f"{who} position table"):
        engine(model, max_length=length)
    built = engine(model, max_length=100, prefill_buckets=(64, 16, 128))
    assert built.max_length == 100
    assert built.prefill_buckets == (16, 64)


def test_cache_geometry():
    spec = {"max_length": 256}
    assert kv_cache.cache_geometry(spec, None, (512, 32)) == (256, (32,))
    assert kv_cache.cache_geometry(spec, 24, (32, 64)) == (24, (24,))
    with pytest.raises(ValueError, match="the draft's position table "
                                         r"\(256 positions\)"):
        kv_cache.cache_geometry(spec, 300, (32,), "draft's")


# ------------------------------------------------------- views and places
@pytest.mark.parametrize("kv_dtype", [None, "int8"])
def test_row_view_and_back(kv_dtype):
    cache = kv_cache.init_cache(_looped(), 3, 16, kv_dtype=kv_dtype)
    view = kv_cache.cache_row_view(cache, jnp.int32(1))
    k = view[0][0]
    rows = jax.tree.leaves(k, is_leaf=lambda x: isinstance(
        x, kv_cache.CacheRow))
    assert all(isinstance(r, kv_cache.CacheRow) for r in rows)
    assert len(rows) == (2 if kv_dtype else 1)
    back = kv_cache.cache_row_buffers(view)
    assert jax.tree.structure(back) == jax.tree.structure(cache)
    assert all(a is b for a, b in zip(jax.tree.leaves(back),
                                      jax.tree.leaves(cache)))


def test_a_quantized_engine_admits_without_a_pool():
    """Since PR 27 the engine had wrapped the rows by hand, and the write
    then took the wrapped int8 entry for a plain one (``AttributeError:
    'tuple' object has no attribute 'dtype'`` at the first admission). A
    view keeps its leaf's dtype, and the tokens are ``generate()``'s."""
    pt.seed(11)
    model = _gpt()
    model.eval()
    prompt = (np.arange(9, dtype=np.int32) * 7) % 1000 + 1
    eng = ContinuousBatchingEngine(model, slots=2, max_length=64,
                                   prefill_buckets=(16,), kv_dtype="int8")
    first, _, _ = eng.admit(Request(prompt=prompt, max_new_tokens=6,
                                    greedy=True, seed=0), 1)
    served = [first] + [eng.step()[0].token for _ in range(5)]
    solo = GenerationEngine(model, max_length=64, prefill_buckets=(16,),
                            kv_dtype="int8").generate(
                                prompt, max_new_tokens=6)
    assert served == [int(t) for t in np.asarray(solo)[0]]


@pytest.fixture
def mesh_dp2_mp2():
    mesh = init_mesh(dp=2, mp=2, devices=jax.devices()[:4])
    yield mesh
    set_mesh(None)


@pytest.mark.parametrize("build,stack", [(_gpt, 0), (_looped, 1)])
def test_constrain_cache_reads_the_leaf(mesh_dp2_mp2, build, stack):
    """Rows, kv heads and the stack come from the leaf itself: inside a
    program every leaf (a scale too) keeps the placement ``init_cache``
    gave it."""
    cache = kv_cache.init_cache(build(), 4, 16, kv_dtype="int8")
    want = kv_cache.cache_sharding_spec(4, 4, stack=stack)
    assert want is not None
    out = jax.jit(lambda c: kv_cache.constrain_cache(
        jax.tree.map(lambda x: x + 1, c)))(cache)
    for got, leaf in zip(jax.tree.leaves(out), jax.tree.leaves(cache)):
        assert leaf.sharding.is_equivalent_to(want, leaf.ndim)
        assert got.sharding.is_equivalent_to(want, got.ndim)
