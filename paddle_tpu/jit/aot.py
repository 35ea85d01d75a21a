"""AOT-compiled program signatures: ``jit.lower().compile()`` with a
self-healing re-lower on input-sharding drift.

Shared by the two places that build long-lived executables ahead of
dispatch and need the ``Lowered``/``Compiled`` stages in hand:

- :class:`~paddle_tpu.jit.to_static.TrainStep` — per-program-kind
  cost/memory attribution (``lowered.cost_analysis()`` /
  ``compiled.memory_analysis()``, PR 4);
- the serving engine (:mod:`paddle_tpu.serving.engine`) — prefill/decode
  programs compiled per bucketed signature at warmup, so the first
  request never pays a trace+compile and the bucket table bounds the
  executable count.

Why not plain ``jax.jit``: dispatch-mode jit hides both stages and
compiles lazily at first call; an AOT ``Compiled`` exposes them but
REFUSES input layouts/shardings that drift from the example arguments
(e.g. ZeRO: XLA re-shards updated params over the zero axis, so step 2's
inputs no longer match step 1's executable — dispatch-mode jit silently
recompiles there). :class:`AOTProgram` does the same healing explicitly:
when a call is refused AND an argument's placement really differs from
what the executable was built for, re-lower/re-compile for the new
placement, and after repeated flip-flops hand the entry to dispatch-mode
jit, whose executable cache holds every layout at once. The drift is
read off the arguments and ``Compiled.input_formats``, never off the
wording of jax's error (which changed between 0.4 and 0.9 and silently
disabled the heal).
"""

from __future__ import annotations

import re
from typing import (Any, Callable, Dict, FrozenSet, List, Optional, Sequence,
                    Tuple)

import jax

from ..nn.layer import BLOCKS

__all__ = ["AOTProgram", "SCOPES", "scopes", "parse_scopes", "index_program",
           "kernel_calls", "products"]

#: HLO module name (``jit_train_step``; a device trace's `XLA Modules`
#: line carries the same) -> ``{instruction name: (block, phase)}`` of
#: the newest executable built under that name: which block of the model
#: (``nn.layer.BLOCKS``) each instruction of the OPTIMIZED program came
#: from, and whether it is forward, backward or recomputed-forward work.
#: Read by whoever splits a device trace by block (the benchmark's
#: ``block.*`` readers). Only this small dict outlives a build, never
#: the executable or its text.
SCOPES: Dict[str, Dict[str, Tuple[str, str]]] = {}

#: HLO module name -> the names of its instructions that are Mosaic
#: (Pallas) kernel calls (``flash_fwd.19``: the kernel's name and XLA's
#: number), of the same executable as :data:`SCOPES`' entry.
KERNEL_CALLS: Dict[str, FrozenSet[str]] = {}

#: HLO module name -> the names of its instructions that run an MXU
#: product or a collective (:func:`index_program` says which), of the
#: same executable as :data:`SCOPES`' entry.
PRODUCTS: Dict[str, FrozenSet[str]] = {}

_MOSAIC_CALL = 'custom_call_target="tpu_custom_call"'
_HLO_MODULE = re.compile(r"^HloModule ([\w.\-]+)")
_HLO_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = ")
_HLO_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) .*\{$")
#: the opcode: the first word after the result's type that opens a
#: parenthesis (a layout's ``T(8,128)`` follows a colon, not a space)
_HLO_OPCODE = re.compile(r"\s([a-z][\w\-]*)\(")
_HLO_CALLS = re.compile(r"calls=%?([\w.\-]+)")
_PRODUCT_OPS = frozenset({"dot", "convolution"})
_COLLECTIVE_OPS = frozenset({
    "all-reduce", "all-gather", "reduce-scatter", "collective-permute",
    "all-to-all"})
_HLO_OP_NAME = re.compile(r"op_name=\"([^\"]*)\"")
_PATH_WORD = re.compile(r"[A-Za-z_]+")
_HLO_OPERAND = re.compile(r"%([\w.\-]+)")
#: ``op_name``s XLA:TPU gives instructions of its own making, which
#: carry no path: the grouped product ``jax.lax.ragged_dot`` is expanded
#: into (a Mosaic kernel call a product)
_COMPILER_MADE = ("ragged-dot",)


def _resolve(op_name: str) -> Optional[Tuple[str, str]]:
    """``jit(train_step)/jit(main)/transpose(jvp(while))/body/
    checkpoint/rematted_computation/attn/dot_general`` ->
    ``("attn", "bwd")``: the block is the INNERMOST vocabulary word of
    the path (wrappers such as ``transpose(jvp(attn))`` hold it inside
    their parentheses); the phase is ``remat`` under a
    ``rematted_computation``, else ``bwd`` under a ``transpose(``, else
    ``fwd``. None when the path holds no block."""
    block = None
    for part in op_name.split("/")[:-1]:       # the last part is the op
        for word in _PATH_WORD.findall(part):
            if word in BLOCKS:
                block = word
    if block is None:
        return None
    phase = ("remat" if "rematted_computation" in op_name
             else "bwd" if "transpose(" in op_name else "fwd")
    return block, phase


def parse_scopes(hlo_text: str) -> Tuple[str, Dict[str, Tuple[str, str]]]:
    """(module name, {instruction name: (block, phase)}) from the text
    of an optimized HLO module (``compiled.as_text()``).

    An instruction resolves by its OWN ``op_name``, with one exception:
    an instruction XLA made itself under a name of its own
    (:data:`_COMPILER_MADE`: ``ragged-dot-none``, the kernel call a
    ``ragged_dot`` becomes on a TPU; 36% of the Command A+ cell's device
    time read as unscoped for it, PERF.md, PR 32) takes the block of the
    instructions that READ it, where they all agree.
    One whose path holds no block (XLA names a fusion after its root,
    and the root may be plumbing; a layout copy carries no metadata at
    all) stays out of the table, and its time reads as unscoped:
    guessing its block from its neighbours moved under 1% of the busy
    time of either train cell (PERF.md, PR 26) and could move time
    between blocks without showing."""
    m = _HLO_MODULE.match(hlo_text)
    table: Dict[str, Tuple[str, str]] = {}
    made: Dict[str, set] = {}           # compiler-made -> its readers' hits
    lines = hlo_text.splitlines()
    for line in lines:
        inst = _HLO_INSTRUCTION.match(line)
        op_name = _HLO_OP_NAME.search(line) if inst else None
        hit = _resolve(op_name.group(1)) if op_name else None
        if hit is not None:
            table[inst.group(1)] = hit
        elif op_name and op_name.group(1).startswith(_COMPILER_MADE):
            made[inst.group(1)] = set()
    if made:
        for line in lines:
            inst = _HLO_INSTRUCTION.match(line)
            hit = table.get(inst.group(1)) if inst else None
            if hit is not None:
                for operand in _HLO_OPERAND.findall(line[inst.end():]):
                    if operand in made:
                        made[operand].add(hit)
        table.update({name: hits.pop() for name, hits in made.items()
                      if len(hits) == 1})
    return (m.group(1) if m else ""), table


def index_program(hlo_text: str) -> str:
    """Keep the scope index, the kernel calls and the products of an
    optimized HLO module (``compiled.as_text()``) under its name, which
    is returned. Every :class:`AOTProgram` build does; a program
    compiled some other way (for a described chip) is read the same way
    through this.

    The products (:data:`PRODUCTS`) are the instructions that run an MXU
    product or a collective: a fusion whose computation holds a ``dot``
    or a ``convolution``, such an instruction unfused, and
    ``all-reduce``, ``all-gather``, ``reduce-scatter``,
    ``collective-permute`` and ``all-to-all`` (their ``-start`` forms;
    never a ``-done``). Instructions INSIDE a fused computation are the
    fusion's own work and are not counted apart."""
    module, table = parse_scopes(hlo_text)
    calls = set()
    body: Dict[str, List[Tuple[str, str, Optional[str]]]] = {}
    where: List[Tuple[str, str, Optional[str]]] = []
    for line in hlo_text.splitlines():          # one pass: calls, bodies
        head = _HLO_COMPUTATION.match(line)
        if head:
            where = body.setdefault(head.group(1), [])
            continue
        inst = _HLO_INSTRUCTION.match(line)
        if inst is None:
            continue
        if _MOSAIC_CALL in line:
            calls.add(inst.group(1))
        code = _HLO_OPCODE.search(line, inst.end() - 1)
        callee = _HLO_CALLS.search(line)
        where.append((inst.group(1), code.group(1) if code else "",
                      callee.group(1) if callee else None))
    fused = {callee for insts in body.values()
             for _, code, callee in insts if code == "fusion"}

    def runs(code: str, callee: Optional[str]) -> bool:
        if code.endswith("-start"):
            code = code[:-len("-start")]
        if code in _PRODUCT_OPS or code in _COLLECTIVE_OPS:
            return True
        return (code == "fusion" and callee is not None
                and any(runs(c, k) for _, c, k in body.get(callee, ())))

    SCOPES[module] = table
    KERNEL_CALLS[module] = frozenset(calls)
    PRODUCTS[module] = frozenset(
        name for comp, insts in body.items() if comp not in fused
        for name, code, callee in insts if runs(code, callee))
    return module


def scopes(module_name: str) -> Optional[Dict[str, Tuple[str, str]]]:
    """The scope index of the newest executable built under this HLO
    module name, or None when none was."""
    return SCOPES.get(module_name)


def _placed(record: Dict[str, FrozenSet[str]], module_name: str,
            block: Optional[str], phase: Optional[str]) -> Optional[List[str]]:
    names = record.get(module_name)
    if names is None:
        return None
    index = SCOPES[module_name]

    def there(name):
        b, p = index.get(name, (None, None))
        return block in (None, b) and phase in (None, p)

    return sorted(filter(there, names))


def kernel_calls(module_name: str, block: Optional[str] = None,
                 phase: Optional[str] = None) -> Optional[List[str]]:
    """The Mosaic kernel calls of the newest executable built under
    this HLO module name, sorted, or None when none was; with ``block``
    and/or ``phase``, those the scope index places there.
    ``kernel_calls("jit_train_step", "attn", "remat")`` is what a
    recomputed layer body runs of attention's kernels AGAIN: nothing
    under the default policy (the flash output and the attention branch
    after the hidden dropout are kept, so neither ``flash_fwd`` nor that
    dropout's ``fused_dropout`` runs twice); under ``"full"`` both, once
    a layer body. An interpreted kernel (the CPU tests) is no call."""
    return _placed(KERNEL_CALLS, module_name, block, phase)


def products(module_name: str, block: Optional[str] = None,
             phase: Optional[str] = None) -> Optional[List[str]]:
    """The MXU products and collectives (:func:`index_program`) of the
    newest executable built under this HLO module name, sorted, or None
    when none was; with ``block`` and/or ``phase``, those the scope
    index places there. ``products("jit_train_step", phase="remat")`` is
    what a recomputed layer body runs again of them: nothing under the
    default policy, the QKV, out-projection and FFN-in products (and,
    on a tensor-parallel mesh, the out-projection's all-reduce) a body
    under ``"full"``. Under ZeRO, the step built again after its first
    call (the parameters then come back sharded) also all-gathers the
    two norms' parameters in its recomputed body, under every policy:
    two instructions that are no product."""
    return _placed(PRODUCTS, module_name, block, phase)


def _inputs_drifted(compiled, args) -> bool:
    """Whether a committed array in ``args`` sits under a sharding or a
    device layout other than the one ``compiled`` was built for — the
    two conditions under which a ``Compiled`` refuses a call whose
    shapes and dtypes match."""
    want = jax.tree_util.tree_leaves(compiled.input_formats[0])
    for arg, fmt in zip(jax.tree_util.tree_leaves(args), want):
        if fmt.sharding is None or not isinstance(arg, jax.Array):
            continue                    # pruned input / host scalar
        if (jax.dtypes.issubdtype(arg.dtype, jax.dtypes.prng_key)
                or not arg.committed):
            continue                    # placed by the call, never refused
        if not arg.sharding.is_equivalent_to(fmt.sharding, arg.ndim):
            return True
        have = arg.format.layout
        if (have is not None and fmt.layout is not None
                and have != fmt.layout):
            return True
    return False


class AOTProgram:
    """One program signature, compiled ahead of time.

    ``name`` (default ``kind``) names the jitted function, hence the
    HLO module (``jit_<name>``) and the program in a device trace; after
    every build the instruction -> block index of the executable is kept
    in :data:`SCOPES` under that module name.

    ``on_attribute(kind, lowered, compiled)`` is called after every
    build (including heals — newest wins), with the exact lowering and
    executable the calls will run; attribution therefore costs no extra
    trace or compile. A compiler refusal (a Mosaic kernel over its VMEM
    limit, a program that does not fit HBM) propagates from
    :meth:`compile` with its own message — it is never retried through
    dispatch-mode jit, which would only fail later and further from the
    cause.
    """

    #: layout flip-flops tolerated under one shape signature before the
    #: entry is handed to dispatch-mode jit for good
    MAX_HEALS = 2

    def __init__(self, kind: str, fn: Callable,
                 donate_argnums: Sequence[int] = (),
                 on_attribute: Optional[Callable[[str, Any, Any], None]]
                 = None, name: Optional[str] = None):
        self.kind = kind
        self.donate_argnums = tuple(donate_argnums)
        # the function's name is the HLO module's (`jit_<name>`), which
        # is how a device trace names the program: stable by kind
        fn.__name__ = fn.__qualname__ = name or kind
        self._jitted = jax.jit(fn, donate_argnums=self.donate_argnums)
        self._on_attribute = on_attribute
        self._compiled: Any = None
        self.heals = 0
        self.builds = 0

    # -- construction ------------------------------------------------------
    def _build(self, args) -> Any:
        from .to_static import _control_flow_guidance
        with _control_flow_guidance():
            lowered = self._jitted.lower(*args)
        compiled = lowered.compile()
        self.builds += 1
        index_program(compiled.as_text())
        if self._on_attribute is not None:
            self._on_attribute(self.kind, lowered, compiled)
        return compiled

    def compile(self, example_args) -> "AOTProgram":
        """Build the executable for the example signature."""
        self._compiled = self._build(example_args)
        return self

    @property
    def compiled(self) -> Any:
        """The ``jax.stages.Compiled`` calls run now (``as_text()``,
        ``memory_analysis()``); None before :meth:`compile` and after
        the hand-off to dispatch-mode jit."""
        return self._compiled

    # -- dispatch ----------------------------------------------------------
    def __call__(self, *args):
        if self._compiled is None:
            return self._jitted(*args)
        try:
            return self._compiled(*args)
        except ValueError:
            # Refused before execution (donated args are intact). Only a
            # placement that moved since this signature was compiled is
            # ours to heal — the drift dispatch-mode jit silently
            # recompiles through; anything else is the caller's error.
            if not _inputs_drifted(self._compiled, args):
                raise
        self.heals += 1
        if self.heals > self.MAX_HEALS:
            # layouts keep flip-flopping under one shape signature:
            # hand the entry to dispatch-mode jit, whose executable
            # cache holds every layout at once
            self._compiled = None
            return self._jitted(*args)
        self._compiled = self._build(args)
        return self._compiled(*args)
