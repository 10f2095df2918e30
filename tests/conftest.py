"""Test harness: run everything on a virtual 8-device CPU mesh.

This is the same pjit/shard_map code path as real TPU hardware (SURVEY.md
section 4 "Distributed tests without a cluster"); only the backend differs.
Must run before the first ``import jax`` anywhere in the test process.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

# Plugins (jaxtyping) import jax before this conftest runs, so the env var
# alone can arrive too late; the config update works until the backend is
# actually initialized, which no plugin does.
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_default_matmul_precision", "highest")
# entry points called in-process (train.main(), generate.main(), ...) place
# the persistent compile cache; keep it switched off in THIS process so a
# warm cache cannot change what later tests compile and count.  CLI tests
# run subprocesses, which do use the cache.
jax.config.update("jax_enable_compilation_cache", False)

import pytest  # noqa: E402


def pytest_collection_modifyitems(config, items):
    """Refuse a fast+slow double-mark at collection time: ``-m fast`` is
    the sub-2-minute tier, and pytest's -m matches ANY marker on the item,
    so a module-level fast mark on a file with slow tests would silently
    drag them in (modules with slow tests must mark fast per-test)."""
    both = [
        item.nodeid for item in items
        if item.get_closest_marker("slow") is not None
        and item.get_closest_marker("fast") is not None
    ]
    if both:  # not an assert: must survive python -O
        raise pytest.UsageError(
            f"tests marked BOTH fast and slow (mark fast per-test in "
            f"modules that contain slow tests): {both[:5]}"
        )


@pytest.fixture
def rng():
    return jax.random.PRNGKey(0)


def _mapped_regions() -> int:
    with open("/proc/self/maps") as f:
        return sum(1 for _ in f)


@pytest.fixture(autouse=True, scope="module")
def _stay_under_max_map_count():
    """Every XLA:CPU executable keeps several memory mappings, and one
    process runs the whole suite: some 470 tests in it reaches the
    kernel's ``vm.max_map_count`` (65,530) and the next compile
    segfaults inside ``backend_compile_and_load``.  Dropping the compiled
    programs gives the mappings back; it is done between modules, and
    only once the count is past half the limit, so nothing recompiles
    that did not have to."""
    yield
    try:
        with open("/proc/sys/vm/max_map_count") as f:
            limit = int(f.read())
        near = _mapped_regions() > limit // 2
    except OSError:  # not Linux: no such limit to watch
        return
    if near:
        jax.clear_caches()


def make_toy_bpe(dirpath, merges=()):
    """Write a valid toy GPT-2 BPE data dir: the 256-byte identity vocab
    plus one vocab entry per merge (ids in rank order — how the real
    vocab lays out its first entries).  Shared by the tokenizer,
    data-prep, and CLI test suites."""
    import json

    from mamba_distributed_tpu.data.gpt2_bpe import bytes_to_unicode

    b2u = bytes_to_unicode()
    vocab = {b2u[i]: i for i in range(256)}
    for a, b in merges:
        vocab.setdefault(a + b, len(vocab))
    os.makedirs(dirpath, exist_ok=True)
    with open(os.path.join(dirpath, "encoder.json"), "w", encoding="utf-8") as f:
        json.dump(vocab, f)
    with open(os.path.join(dirpath, "vocab.bpe"), "w", encoding="utf-8") as f:
        f.write("#version: 0.2\n")
        for a, b in merges:
            f.write(f"{a} {b}\n")
    return str(dirpath)
