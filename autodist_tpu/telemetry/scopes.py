"""Names inside the compiled programs, and the way back from a device
trace to them.

Host spans (:mod:`~autodist_tpu.telemetry.spans`) say what the host did;
this module is the device-side half. Three pieces:

- **One table of scope names** (:data:`SCOPES`) and :func:`scope`, the
  only place in the package that calls ``jax.named_scope``. A scope is
  HLO metadata: it names the ``op_name`` of every instruction traced
  under it and costs the device nothing, so scopes are always on. JAX
  wraps the scopes of a differentiated function itself: forward ops of
  the loss read ``jvp(loss)/...``, backward ops
  ``transpose(jvp(loss))/...``, recomputed ones carry
  ``rematted_computation``.
- **The map from HLO instruction name to ``op_name``**
  (:func:`scope_map`). A profiler's device events carry only the
  instruction's name (``fusion.37``); the compiled module's text says
  which source scope each instruction came from. The owners of compiled
  programs (``Runner``, ``DecodeEngine``) register how to lower each of
  theirs under the XLA module's name; the map is computed ON DEMAND —
  one extra lowering and compile, with both compile caches bypassed —
  and never on a step's path.
- **The step account** (:func:`step_account`): what that same compile
  says the program holds on a device (scratch, arguments, outputs,
  aliases, generated code), beside the map. The compile is done once per
  registered program and both products are kept.

Why the caches are bypassed: ``jax_compilation_cache_include_metadata_in_key``
is False, so the persistent cache hands a process an executable that an
earlier process (another checkout of this tree, say) compiled, whose text
carries THAT process's ``op_name``s; and JAX's in-memory cache answers a
compile of what the jit already ran with that same executable.
Instruction names do not depend on metadata, so a fresh compile of this
tree's lowering names the same instructions the running executable has,
with this tree's scopes.
"""
import contextlib
import functools
import re
import threading
import weakref
from typing import Callable, Dict, List, Optional

# ------------------------------------------------------------ scope table
#
# name -> what is traced under it. Call sites use the constants, so a
# grep for a scope finds its sites and a typo is an AttributeError.

PARAMS = "params"
LOSS = "loss"
GRAD_SYNC = "grad_sync"
OPTIMIZER = "optimizer"
SENTINEL = "sentinel"
LEAN_HEAD = "lean_head"
LEAN_HEAD_BWD = "lean_head_bwd"
EMBED = "embed"
BLOCKS = "blocks"
ATTENTION = "attention"
ATTN_CORE = "attn_core"
ATTN_GATE = "attn_gate"
DENSE_FFN = "dense_ffn"
PLAIN_HEAD = "plain_head"
MOE = "moe"
MOE_ROUTE = "moe_route"
MOE_EXPERTS = "moe_experts"
MOE_SHARED = "moe_shared"
KDA = "kda"
KDA_SCAN = "kda_scan"
MLA = "mla"
MLA_CORE = "mla_core"
DSA_INDEX = "dsa_index"
DSA_TOPK = "dsa_topk"
DSA_CORE = "dsa_core"
SWA_CORE = "swa_core"
CONV_MIX = "conv_mix"
CONV_CORE = "conv_core"
MAMBA = "mamba"
SSD_SCAN = "ssd_scan"
LOOP = "loop"
EXIT_GATE = "exit_gate"
PREFILL = "prefill"
DECODE = "decode"
INSERT = "insert"

SCOPES: Dict[str, str] = {
    PARAMS: "parameter gather into the full layout and the host-PS "
            "de-wire, at the top of every compiled program",
    LOSS: "the user's loss; under value_and_grad JAX writes jvp(loss) on "
          "its forward ops and transpose(jvp(loss)) on its backward ops",
    GRAD_SYNC: "everything between the raw gradients and the synced ones: "
               "buckets, per-variable syncs, ZeRO reduce-scatters, the "
               "sparse wire, host-PS gradient reduction, casts and scaling",
    OPTIMIZER: "optimizer update and apply (ZeRO shard update and its "
               "all-gather included)",
    SENTINEL: "the health sentinel's in-graph verdict and select",
    LEAN_HEAD: "ops/xent.py chunked head, forward (and the part of its "
               "backward JAX derives itself)",
    LEAN_HEAD_BWD: "ops/xent.py chunked head, the custom_vjp backward rule",
    EMBED: "token and position embedding of the LM",
    BLOCKS: "the transformer blocks of the LM",
    ATTENTION: "multi-head attention inside a block",
    ATTN_CORE: "inside attention: every call of a softmax attention core on "
               "q, k, v, whatever the mixer (plain heads, grouped or chosen "
               "keys with dsa_core, a window's band with swa_core, a latent "
               "mixer's with mla_core, the "
               "cached decode step's): the flash kernels on a TPU, XLA's "
               "scores elsewhere; KDA, the short convolution and Mamba-2 "
               "have no such core",
    ATTN_GATE: "inside attention, outside attn_core: a GATED softmax "
               "attention's output gate (afmoe's): the gate projection of "
               "the layer's normed input to every head's features, its "
               "sigmoid and the product with the core's output, before the "
               "output projection",
    DENSE_FFN: "inside blocks: a block's DENSE feed-forward alone, the "
               "SwiGLU of a leading dense layer or the two-matmul GELU one; "
               "its pre-norm, a sandwich norm, dropout and the residual add "
               "lie outside, a shared expert stays under moe_shared",
    PLAIN_HEAD: "inside loss: the head that makes the full [tokens, vocab] "
                "logits and the log-softmax and target pick that follow it "
                "(a step has this or the lean head's two)",
    MOE: "the routed feed-forward of a block (parallel/expert.py "
         "dropless_moe_ffn), its router losses included",
    MOE_ROUTE: "inside moe: router matmul, softmax, top-k, the sort by "
               "expert, the row gather, and the un-sort and gated combine",
    MOE_EXPERTS: "inside moe: the three grouped matmuls over the sorted "
                 "rows and the SwiGLU activation between them (a layer that "
                 "holds a share of its experts: every held expert on every "
                 "token)",
    MOE_SHARED: "inside moe: the shared expert(s), a dense SwiGLU every token "
                "passes",
    KDA: "inside attention: a Kimi Delta Attention mixer (projections, short "
         "convolutions, gates, the delta rule, the gated norm, the output "
         "projection)",
    KDA_SCAN: "inside kda: the chunked gated delta rule alone "
              "(ops/kda.py): at heads of whole 128-lane tiles the pallas "
              "kernels kda_fwd / kda_bwd and the layout passes around them",
    MLA: "inside attention: a latent-attention mixer (q, the latent and its "
         "up-projection, the attention core, the output projection)",
    MLA_CORE: "inside mla: the attention core alone, the call of the "
              "attention function on q, k, v (the flash kernels flash_fwd / "
              "flash_bwd on a TPU, XLA's scores elsewhere)",
    DSA_INDEX: "inside attention: a sparse attention's indexer, its three "
               "projections, the key's LayerNorm, the rotation and the index "
               "scores of each block of queries (ops/dsa.py), all float32",
    DSA_TOPK: "inside attention: the choice alone, each query's topk keys "
              "of a block's index scores (ops/dsa.py:choose); a recomputed "
              "block holds none",
    DSA_CORE: "inside attention: the attention core over grouped K/V heads "
              "and, with an indexer, the chosen keys alone (the flash kernels "
              "with a selection on a TPU, XLA's scores elsewhere)",
    SWA_CORE: "inside attention: the attention core of a SLIDING-WINDOW layer "
              "(a query sees the latest window keys, itself counted), grouped "
              "K/V heads or not: on a TPU the flash kernels over the tiles "
              "the band touches alone, XLA's masked scores elsewhere; a "
              "model's global layers stay under dsa_core or bare attn_core",
    CONV_MIX: "inside attention: a gated short convolution mixer (the "
              "input projection to three times the width, the two gates, the "
              "depthwise causal convolution, the output projection)",
    CONV_CORE: "inside conv_mix: what lies between the two projections, the "
               "split in thirds, the two element-wise gates and the short "
               "depthwise causal convolution",
    MAMBA: "inside attention: a Mamba-2 mixer whole (the input projection, "
           "the short causal filter with its bias and SiLU, the state-space "
           "recurrence, the gated grouped norm, the output projection)",
    SSD_SCAN: "inside mamba: the recurrence's core alone (ops/ssd.py): the "
              "decays, the chunked dual form's four products, the states "
              "carried from chunk to chunk and D x (the ssd_fwd / ssd_bwd "
              "kernels and the layout passes made for them where the heads "
              "fill whole tiles, the jnp form elsewhere)",
    LOOP: "inside blocks: the passes of a looped model (LMConfig.loop_steps), "
          "every block and the final norm once a pass over shared weights "
          "(one body traced, compiled as straight-line code)",
    EXIT_GATE: "a looped model's exit gate in the loss: the gate's logits "
               "on the passes' normed states, the exit distribution, its "
               "entropy and the weighted sum of the passes' losses",
    PREFILL: "serving: the prompt pass of a prefill bucket",
    DECODE: "serving: one cached decode step",
    INSERT: "serving: writing admitted rows into the decode state",
}


def scope(name: str):
    """``jax.named_scope(name)`` for a name of the table."""
    if name not in SCOPES:
        raise KeyError("scope %r is not in telemetry.scopes.SCOPES" % name)
    import jax
    return jax.named_scope(name)


def scoped(name: str):
    """Decorator: trace the whole function under ``scope(name)``."""
    def wrap(f):
        @functools.wraps(f)
        def under_scope(*args, **kwargs):
            with scope(name):
                return f(*args, **kwargs)
        return under_scope
    return wrap


# ------------------------------------------------------- program registry
#
# XLA module name -> how to lower that program again. Owners register a
# bound method weakly (the owner holds device state; the registry must
# not keep it alive), the newest registration of a name wins.

_programs: Dict[str, Callable[[], Optional[Callable]]] = {}
_accounts: Dict[str, dict] = {}  # name -> step_account()'s value
_lock = threading.Lock()


def register_program(module_name: str, lower: Callable) -> None:
    """Make ``module_name`` (``"jit_local_step"``) inspectable.

    ``lower()`` returns the program's ``jax.stages.Lowered`` for the
    arguments it runs with; it is called only by :func:`scope_map` and
    :func:`step_account`. A bound method is held weakly."""
    ref = (weakref.WeakMethod(lower) if hasattr(lower, "__self__")
           else (lambda: lower))
    with _lock:
        _programs[module_name] = ref
        _accounts.pop(module_name, None)


def registered_programs() -> List[str]:
    with _lock:
        return sorted(n for n, ref in _programs.items() if ref() is not None)


# ``lowered.compile()`` of a lowering the jit already ran is answered from
# JAX's in-memory cache with the RUNNING executable (which may be one the
# persistent cache loaded, with another process's metadata). Compiler
# options are part of that cache's key: one debug option, set to its
# default, asks for a compile of its own and changes nothing in it.
_FRESH_COMPILE = {"xla_dump_disable_metadata": False}


@contextlib.contextmanager
def _persistent_cache_bypassed():
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()  # the decision to use the cache is memoised
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", before)
        cc.reset_cache()


def compiled(module_name: str):
    """A registered program's ``jax.stages.Compiled``, from a compile of
    THIS process's lowering with the persistent cache bypassed; None
    where no live owner registered the name. The one place that pays."""
    with _lock:
        ref = _programs.get(module_name)
    lower = ref() if ref is not None else None
    if lower is None:
        return None
    with _persistent_cache_bypassed():
        return lower().compile(compiler_options=_FRESH_COMPILE)


# the account's name of a field -> ``CompiledMemoryStats``'s
_MEMORY_FIELDS = {"temp_bytes": "temp_size_in_bytes",
                  "argument_bytes": "argument_size_in_bytes",
                  "output_bytes": "output_size_in_bytes",
                  "alias_bytes": "alias_size_in_bytes",
                  "code_bytes": "generated_code_size_in_bytes",
                  "peak_bytes": "peak_memory_in_bytes"}


def _memory(fresh) -> Optional[Dict[str, Optional[int]]]:
    """``Compiled.memory_analysis()`` field for field (a field the
    backend does not give is None); None where it gives no analysis."""
    stats = fresh.memory_analysis()
    if stats is None:
        return None
    return {ours: getattr(stats, xla, None)
            for ours, xla in _MEMORY_FIELDS.items()}


def step_account(module_name: str = "jit_local_step") -> Optional[dict]:
    """What the compiled program holds, read off ONE fresh compile on first
    use and kept until the name is registered anew:
    ``{"module", "memory", "instructions"}``. ``memory`` is the COMPILER's
    word on the program that runs, per device as XLA reports it for the
    partitioned program: ``temp_bytes`` (the step's scratch, which
    ``memory_stats()`` leaves out), ``argument_bytes``, ``output_bytes``,
    ``alias_bytes`` (outputs that reuse an argument's buffer),
    ``code_bytes`` and ``peak_bytes`` (None where the backend gives none);
    None for ``memory`` on a backend without an analysis, never a guess.
    ``instructions`` is :func:`scope_map`'s value: asking for either after
    the other compiles nothing more. None where the program is not
    registered."""
    with _lock:
        got = _accounts.get(module_name)
    if got is not None:
        return got
    fresh = compiled(module_name)
    if fresh is None:
        return None
    got = {"module": module_name, "memory": _memory(fresh),
           "instructions": parse_scope_map(fresh.as_text())}
    with _lock:
        _accounts[module_name] = got
    return got


def scope_map(module_name: str) -> Optional[Dict[str, List[str]]]:
    """``{HLO instruction name: [op_name, ...]}`` of a registered program
    (see :func:`parse_scope_map` for the value), computed on first use
    and kept (the account's ``instructions``); None where the program is
    not registered."""
    account = step_account(module_name)
    return None if account is None else account["instructions"]


# ------------------------------------------------------------ HLO parsing

_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*->.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT\s+)?%?([\w.\-]+)\s*=\s")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_FUSION_CALLS = re.compile(r"\sfusion\(.*\bcalls=%?([\w.\-]+)")


def parse_scope_map(hlo_text: str) -> Dict[str, List[str]]:
    """Raw ``op_name`` metadata per instruction of an optimized HLO module.

    The value is a list of strings exactly as the compiler printed them:
    for a plain instruction its own ``op_name`` (an empty list where it
    has none: parameters, compiler-made copies); for a FUSION its own
    ``op_name`` first (``""`` where it has none) and then the ``op_name``
    of every fused instruction that carries one. A fusion's own metadata
    is its root's; its members routinely come from several scopes (the
    last backward op with the optimizer's update), and which of them
    names the fusion is the reader's rule, not this module's: the strings
    are the program's, a classification is the consumer's. Instructions
    of while bodies, branches and called computations are separate device
    events and separate entries."""
    members: Dict[str, List[str]] = {}   # computation -> members' op_names
    own: Dict[str, Optional[str]] = {}
    calls: Dict[str, str] = {}
    current = None
    for line in hlo_text.splitlines():
        if not line.startswith((" ", "\t")):
            m = _COMPUTATION.match(line)
            current = m.group(1) if m else None
            if current is not None:
                members[current] = []
            continue
        m = _INSTRUCTION.match(line)
        if m is None or current is None:
            continue
        name = m.group(1)
        op = _OP_NAME.search(line)
        own[name] = op.group(1) if op else None
        if op:
            members[current].append(op.group(1))
        fused = _FUSION_CALLS.search(line)
        if fused:
            calls[name] = fused.group(1)
    # (a fused computation's members are no device events of their own;
    # their entries are harmless: names are unique module-wide)
    return {name: ([op or ""] + members.get(calls[name], [])
                   if name in calls else [op] if op else [])
            for name, op in own.items()}
