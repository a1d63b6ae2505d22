"""Repo-tuned scoping: rule scopes, whitelists, oracles, jitted callees.

Everything path-like is a posix-style path relative to the repo root.
Keeping the tuning here (rather than inside the rules) makes each rule a
pure pattern matcher and leaves one auditable place that says *where*
each contract is binding and *who* is exempt, and why.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

# -- rule scopes ------------------------------------------------------------
# rule id -> path prefixes (or exact files) the rule is binding in.
# None means "everywhere the CLI is pointed at".
SCOPES: Dict[str, Optional[Tuple[str, ...]]] = {
    # Version-drifting jax APIs must route through repro.compat — binding
    # repo-wide; compat.py itself is the whitelisted implementation site.
    "R001": None,
    # The MapReduce memory model is a contract of the algorithm/data
    # layers; examples and benchmarks may deliberately materialize small
    # references (they print and compare against oracles).
    "R002": ("src/repro/core/", "src/repro/data/"),
    # Blocking-invariant sampling: binding on the streamed algorithm
    # paths and the sampler engine itself. serve/ and models/ draw from
    # jax.random by design (per-request sampling is not blocked data).
    "R003": ("src/repro/core/", "src/repro/kernels/engine.py"),
    # Recompile hazards matter wherever ragged block streams meet jitted
    # callees.
    "R004": ("src/repro/", "benchmarks/", "examples/"),
    # Philox limb arithmetic lives in exactly one module.
    "R005": ("src/repro/kernels/engine.py",),
}

# -- whole-file whitelists --------------------------------------------------
# rule id -> exact relpaths exempt from that rule.
WHITELIST: Dict[str, Tuple[str, ...]] = {
    # compat.py is the one sanctioned home of the drifting symbols.
    "R001": ("src/repro/compat.py",),
}

# Files reprolint skips entirely (generated/vendored — none today).
SKIP_FILES: Tuple[str, ...] = ()

# -- R002: declared oracle functions ---------------------------------------
# Functions allowed to touch all n rows. Any function *named*
# ``materialize`` is an oracle by definition (it IS the sanctioned
# escape hatch of the PointSource protocol). Beyond that, whole
# functions are listed here — (relpath, qualname) -> justification —
# when materializing is their documented job; one-line device-path
# branches inside otherwise-streamed functions use inline suppressions
# instead, so the exemption stays exactly as wide as the contract.
ORACLES: Dict[Tuple[str, str], str] = {
    ("src/repro/core/executor.py", "SimExecutor.run_blocks"):
        "SimExecutor simulates m machines on one device: materialize + "
        "block is its documented semantics (ARCHITECTURE.md, Executors).",
    ("src/repro/core/executor.py", "SimExecutor._blocked_for"):
        "the weakref-cached materialize+block behind SimExecutor's EIM "
        "filter rounds — same contract as run_blocks.",
    ("src/repro/core/executor.py", "MeshExecutor._mrg_fused"):
        "the fused single-dispatch MRG path shards a device-resident "
        "copy across the mesh; whole-array residency is its premise "
        "(tested for parity against the streamed path).",
}

ORACLE_NAMES: Tuple[str, ...] = ("materialize",)

# Names that look like whole-source bindings for the asarray pattern.
SOURCE_NAMES: Tuple[str, ...] = ("source", "src")
SOURCE_SUFFIXES: Tuple[str, ...] = ("_source", "_src")

# -- R003: jax.random key management (allowed) vs draws (forbidden) --------
KEY_OPS: Tuple[str, ...] = (
    "PRNGKey", "key", "split", "fold_in", "key_data", "wrap_key_data",
    "clone", "key_impl", "default_prng_impl", "KeyArray",
)

# -- R004: block-stream producers and known-jitted callees -----------------
# Iterating these produces ragged (tail-short) blocks. stream_device /
# zip_shard_blocks / _stream_steps are deliberately absent: they yield
# pre-padded fixed-shape steps (that is their whole point).
RAGGED_STREAMS: Tuple[str, ...] = (
    "blocks", "host_blocks", "_blocks", "_source_blocks",
)

# Callees known to be jitted but defined in another module (module-local
# jit decorations/wrappings are auto-detected by the rule). The fused
# streamed-tile entry points (kernels/fused_stream.py + engine.py) are
# jitted on (rank/bn/interpret)-static signatures: feeding them raw ragged
# tail blocks would recompile per tail shape, so R004 demands the
# pad-to-fixed-rows dance wherever a ragged stream reaches them.
JITTED_CALLEES: Tuple[str, ...] = (
    "bernoulli_rows_at_block",
    "eim_filter_block", "_eim_filter_block",
    "fused_filter_blocks", "fused_assign_blocks", "fused_argmin_blocks",
    # The weighted sibling of fused_filter_blocks (one extra (bn,) weight
    # operand, same (rank/bn/interpret)-static jit signature): the same
    # ragged-tail recompile hazard, so the same pad-dance obligation.
    "fused_filter_blocks_w",
    # The serving query entry point (kernels/engine.py): eager rather than
    # jitted, but shape-signature-sensitive all the same — its recompile
    # discipline rests on callers padding to the fixed (query-bucket,
    # center-bucket) shapes, so ragged streams must do the pad dance
    # before reaching it (serve/kcenter.py does).
    "assign_bucketed",
)

# Call names that sanitize a ragged block (pad-to-``rows`` family).
PAD_CALLS: Tuple[str, ...] = ("pad",)

# -- R005: Philox helper selection -----------------------------------------
# Function names whose bodies must stay pure uint32. The host-side
# splitters (uniform_rows's start>>32, split_index_words's np.uint64)
# are deliberately OUT of scope: they run in Python/NumPy on the host
# before anything reaches the device, where x64 is always available.
PHILOX_FUNC_PREFIXES: Tuple[str, ...] = (
    "_philox", "_mulhilo", "_uniform_rows_words", "_uniform_at_words",
)

WIDE_DTYPES: Tuple[str, ...] = ("int64", "uint64", "float64")


def in_scope(rule_id: str, relpath: str) -> bool:
    if relpath in SKIP_FILES:
        return False
    scope = SCOPES.get(rule_id)
    if scope is None:
        return True
    return any(
        relpath == s or (s.endswith("/") and relpath.startswith(s))
        for s in scope
    )


def file_whitelisted(relpath: str) -> bool:
    return relpath in SKIP_FILES


def rule_whitelisted(rule_id: str, relpath: str) -> bool:
    return relpath in WHITELIST.get(rule_id, ())


def is_source_name(name: str) -> bool:
    return name in SOURCE_NAMES or name.endswith(SOURCE_SUFFIXES)


def oracle_justification(relpath: str, qualname: str) -> Optional[str]:
    return ORACLES.get((relpath, qualname))
