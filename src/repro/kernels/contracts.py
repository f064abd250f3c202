"""Kernel contracts, the device-layout table, and the runtime shape
witness (DESIGN.md §15).

The static ``kernels`` pass (``repro.analysis.passes_kernels``) proves
what it can about every ``pl.pallas_call`` site from the AST; this module
is its runtime counterpart, mirroring the lock-witness split of
``repro.obs.locks``: declarations live next to the code they constrain,
production pays (almost) nothing, and CI arms a process-wide witness
around the fast suite.

* :data:`LAYOUT_CONTRACTS` — the declared dtype+rank of every array in
  the :class:`~repro.core.batch_query.DeviceIndex` layout. The static
  layout-contract rule cross-checks construction sites against this
  table; :func:`check_layout` validates the actual host arrays on upload
  when the witness is armed.
* :func:`kernel_contract` — decorator for the Pallas wrappers in this
  package. It always registers the declaration in :data:`CONTRACTS`
  (so coverage is assertable without arming anything) and attaches it as
  ``__kernel_contract__``; per call it is a no-op unless
  ``REPRO_KERNEL_WITNESS=1`` — unlike the lock witness the flag is read
  at *call* time, because kernels are module-level functions decorated
  once at import while locks are constructed per object. One env read
  per kernel launch is noise next to the launch itself.
* :class:`KernelWitness` — records every armed call, validates array
  rank/dtype/symbolic-dim bindings against the contract, evaluates the
  declared VMEM bound against the per-platform budget, and deduplicates
  violations into a JSON-able report. ``tests/conftest.py`` fails the
  suite on any problem, exactly like the lock gate.

Imports here are numpy-only: the analysis pass imports this module for
:data:`LAYOUT_CONTRACTS` and must not drag jax into a lint run.
"""

from __future__ import annotations

import dataclasses
import functools
import inspect
import os
import threading
from typing import Callable, Mapping, Sequence

import numpy as np

_ENV_FLAG = "REPRO_KERNEL_WITNESS"
_BUDGET_ENV = "REPRO_KERNEL_VMEM_BUDGET"

#: default per-step VMEM budget: ~16 MiB/core on current TPUs (the
#: compiler reserves some; kernels should stay well under). Overridable
#: per-process via REPRO_KERNEL_VMEM_BUDGET, per-run via pyproject for
#: the static estimator.
DEFAULT_VMEM_BUDGET = 16 * 1024 * 1024

#: dtype families for contract specs
ANY_INT = ("int32", "int64", "int16", "int8", "uint32", "uint8")
ANY_FLOAT = ("float32", "bfloat16", "float16", "float64")
INT_OR_BOOL = ANY_INT + ("bool",)


def witness_enabled() -> bool:
    """True when the process-wide kernel witness is armed (checked per
    call, so a long-lived process can arm without re-importing)."""
    return os.environ.get(_ENV_FLAG, "") not in ("", "0", "false", "no")


class KernelContractViolation(Exception):
    """Raised by the conftest session gate when an armed run recorded
    contract problems."""


# ---------------------------------------------------------------------------
# declarations
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ArraySpec:
    """Declared shape+dtype of one kernel operand or output.

    ``dims`` entries are either exact ints or symbol strings bound at
    validation time — first from same-named scalar int arguments, then
    from the first array dim they appear at; every later occurrence must
    agree, which is how cross-operand constraints (label/link/active rows
    all (B, N)) are expressed. ``dtypes`` is the set of accepted dtype
    names."""

    dims: tuple
    dtypes: tuple[str, ...]

    def describe(self) -> str:
        return (f"({', '.join(str(d) for d in self.dims)})"
                f":{'|'.join(self.dtypes)}")


@dataclasses.dataclass(frozen=True)
class KernelContract:
    """The declared interface of one Pallas wrapper."""

    name: str
    in_specs: tuple[tuple[str, ArraySpec], ...]   # (param name, spec)
    out_specs: tuple[ArraySpec, ...]
    #: bound-arguments dict -> worst-case per-step VMEM bytes
    vmem_bound: Callable[[dict], int] | None = None


#: every decorated wrapper's declaration, keyed by qualified name —
#: lets tests assert that each Pallas wrapper carries a contract without
#: arming the witness.
CONTRACTS: dict[str, KernelContract] = {}


#: The device-layout table: dtype + rank of every array entering
#: ``to_device`` / ``_host_layout`` (DESIGN.md §15.4). The static
#: layout-contract rule checks construction sites against this both ways
#: (undeclared keys, missing keys, unprovable dtypes); the armed witness
#: checks the real arrays on upload.
LAYOUT_CONTRACTS: dict[str, tuple[str, int]] = {
    "node_u": ("int32", 1),
    "node_v": ("int32", 1),
    "node_ct": ("int32", 1),
    "live_from": ("int32", 1),
    "live_to": ("int32", 1),
    "row_ptr": ("int32", 1),
    "ent_ts": ("int32", 1),
    "ent_left": ("int32", 1),
    "ent_right": ("int32", 1),
    "ent_parent": ("int32", 1),
    "vrow_ptr": ("int32", 1),
    "vent_ts": ("int32", 1),
    "vent_node": ("int32", 1),
    "ver_ts_from": ("int32", 1),
    "ver_ts_to": ("int32", 1),
    "ver_ct": ("int32", 1),
    "ver_src": ("int32", 1),
    "ver_k": ("int32", 1),
    "knode_ptr": ("int32", 1),
    "live_ptr": ("int32", 1),
    "live_node": ("int32", 1),
}


# ---------------------------------------------------------------------------
# the witness
# ---------------------------------------------------------------------------

def _dtype_name(value) -> str:
    return str(getattr(value, "dtype", type(value).__name__))


class KernelWitness:
    """Validates armed kernel calls against their contracts and records a
    process-wide report.

    Thread-safe; violations are deduplicated by (kind, kernel, message)
    so a hot loop cannot grow the report without bound. The VMEM budget
    is compared against each call's *declared* bound — the witness
    checks the contract's model, the static pass checks the code against
    the same model, and together a kernel whose tiles outgrow VMEM fails
    in CI before it ever runs on hardware."""

    def __init__(self, vmem_budget: int | None = None):
        self.vmem_budget = (vmem_budget if vmem_budget is not None
                            else int(os.environ.get(_BUDGET_ENV,
                                                    DEFAULT_VMEM_BUDGET)))
        self._mu = threading.Lock()
        # kernel name -> {"calls": int, "max_vmem": int}
        self._kernels: dict[str, dict] = {}
        # (kind, kernel, message) -> {"count": int, ...}
        self._violations: dict[tuple[str, str, str], dict] = {}
        self.calls = 0

    # -- recording --------------------------------------------------------
    def on_call(self, kernel: str, vmem_bytes: int | None) -> None:
        with self._mu:
            self.calls += 1
            entry = self._kernels.setdefault(
                kernel, {"calls": 0, "max_vmem": 0})
            entry["calls"] += 1
            if vmem_bytes is not None:
                entry["max_vmem"] = max(entry["max_vmem"], int(vmem_bytes))

    def note(self, kind: str, kernel: str, message: str) -> None:
        with self._mu:
            v = self._violations.setdefault(
                (kind, kernel, message),
                {"kind": kind, "kernel": kernel, "message": message,
                 "count": 0})
            v["count"] += 1

    # -- validation -------------------------------------------------------
    def validate_arrays(self, kernel: str,
                        named: Sequence[tuple[str, object, ArraySpec]],
                        symbols: dict[str, int]) -> None:
        """Check (label, array, spec) triples, binding/checking symbolic
        dims through the shared ``symbols`` map."""
        for label, arr, spec in named:
            shape = getattr(arr, "shape", None)
            if shape is None:
                self.note("shape-contract", kernel,
                          f"{label}: expected an array with .shape, got "
                          f"{type(arr).__name__}")
                continue
            if len(shape) != len(spec.dims):
                self.note("shape-contract", kernel,
                          f"{label}: rank {len(shape)} != declared rank "
                          f"{len(spec.dims)} {spec.describe()}")
                continue
            for dim, actual in zip(spec.dims, shape):
                actual = int(actual)
                if isinstance(dim, int):
                    if actual != dim:
                        self.note("shape-contract", kernel,
                                  f"{label}: dim {actual} != declared "
                                  f"{dim} in {spec.describe()}")
                elif dim in symbols:
                    if actual != symbols[dim]:
                        self.note("shape-contract", kernel,
                                  f"{label}: dim {dim}={actual} "
                                  f"conflicts with {dim}="
                                  f"{symbols[dim]} bound earlier")
                else:
                    symbols[dim] = actual
            dt = _dtype_name(arr)
            if dt not in spec.dtypes:
                self.note("dtype-contract", kernel,
                          f"{label}: dtype {dt} not in declared "
                          f"{{{'|'.join(spec.dtypes)}}}")

    def validate_vmem(self, kernel: str, vmem_bytes: int) -> None:
        if vmem_bytes > self.vmem_budget:
            self.note("vmem-budget", kernel,
                      f"declared per-step VMEM bound {vmem_bytes} B "
                      f"exceeds the budget {self.vmem_budget} B")

    # -- reading ----------------------------------------------------------
    def problems(self) -> list[dict]:
        with self._mu:
            return [dict(v) for v in self._violations.values()]

    def report(self) -> dict:
        """JSON-able summary (written as a CI artifact)."""
        with self._mu:
            kernels = {k: dict(v) for k, v in sorted(self._kernels.items())}
        return {
            "vmem_budget": self.vmem_budget,
            "calls": self.calls,
            "contracts": sorted(CONTRACTS),
            "kernels": kernels,
            "problems": self.problems(),
        }

    def reset(self) -> None:
        with self._mu:
            self._kernels.clear()
            self._violations.clear()
            self.calls = 0


#: Process-wide witness the armed wrappers report into.
WITNESS = KernelWitness()


def _active_witness() -> KernelWitness | None:
    return WITNESS if witness_enabled() else None


# ---------------------------------------------------------------------------
# the decorator
# ---------------------------------------------------------------------------

def _validate_call(contract: KernelContract, witness: KernelWitness,
                   fn: Callable, args: tuple, kwargs: dict):
    try:
        bound = inspect.signature(fn).bind(*args, **kwargs)
        bound.apply_defaults()
        values = dict(bound.arguments)
    except TypeError:
        # a mis-called wrapper fails in fn itself with the real traceback
        return fn(*args, **kwargs)

    # symbols seed: scalar int args whose names appear in the specs
    symbols: dict[str, int] = {}
    spec_syms = {d for _, s in contract.in_specs for d in s.dims
                 if isinstance(d, str)}
    spec_syms |= {d for s in contract.out_specs for d in s.dims
                  if isinstance(d, str)}
    for name, val in values.items():
        if (name in spec_syms and isinstance(val, int)
                and not isinstance(val, bool)):
            symbols[name] = val

    witness.validate_arrays(
        contract.name,
        [(name, values.get(name), spec) for name, spec in contract.in_specs
         if values.get(name) is not None],
        symbols)

    vmem = None
    if contract.vmem_bound is not None:
        try:
            vmem = int(contract.vmem_bound(values))
        except Exception as e:  # a broken bound is itself a finding
            witness.note("vmem-budget", contract.name,
                         f"vmem_bound raised {type(e).__name__}: {e}")
        else:
            witness.validate_vmem(contract.name, vmem)
    witness.on_call(contract.name, vmem)

    out = fn(*args, **kwargs)
    if contract.out_specs:
        outs = out if isinstance(out, tuple) else (out,)
        witness.validate_arrays(
            contract.name,
            [(f"out[{i}]", o, spec)
             for i, (o, spec) in enumerate(zip(outs, contract.out_specs))],
            symbols)
    return out


def kernel_contract(*, in_specs: Mapping[str, ArraySpec],
                    out_specs: Sequence[ArraySpec] | ArraySpec = (),
                    vmem_bound: Callable[[dict], int] | None = None):
    """Declare a Pallas wrapper's interface and arm it for the witness.

    Always registers the contract (coverage is checkable unarmed); the
    per-call validation path only runs under ``REPRO_KERNEL_WITNESS=1``.
    """
    if isinstance(out_specs, ArraySpec):
        out_specs = (out_specs,)

    def deco(fn: Callable) -> Callable:
        contract = KernelContract(
            name=fn.__name__,
            in_specs=tuple(in_specs.items()),
            out_specs=tuple(out_specs),
            vmem_bound=vmem_bound)
        CONTRACTS[fn.__name__] = contract

        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            witness = _active_witness()
            if witness is None:
                return fn(*args, **kwargs)
            return _validate_call(contract, witness, fn, args, kwargs)

        wrapper.__kernel_contract__ = contract
        wrapper.__wrapped__ = fn
        return wrapper

    return deco


# ---------------------------------------------------------------------------
# device-layout validation
# ---------------------------------------------------------------------------

def check_layout(arrays: Mapping[str, object],
                 witness: KernelWitness | None = None) -> list[str]:
    """Cross-check a host layout dict against :data:`LAYOUT_CONTRACTS`
    both ways (undeclared / missing keys, dtype, rank). Returns the
    problem strings; when a witness is given they are also recorded as
    ``layout-contract`` violations. ``to_device`` calls this on every
    upload while the witness is armed."""
    problems: list[str] = []
    for name in arrays:
        if name not in LAYOUT_CONTRACTS:
            problems.append(f"{name}: not declared in LAYOUT_CONTRACTS")
    for name, (dtype, rank) in LAYOUT_CONTRACTS.items():
        if name not in arrays:
            problems.append(f"{name}: declared but absent from the layout")
            continue
        arr = np.asarray(arrays[name])
        if str(arr.dtype) != dtype:
            problems.append(
                f"{name}: dtype {arr.dtype} != declared {dtype}")
        if arr.ndim != rank:
            problems.append(f"{name}: rank {arr.ndim} != declared {rank}")
    if witness is not None:
        for p in problems:
            witness.note("layout-contract", "to_device", p)
    return problems
