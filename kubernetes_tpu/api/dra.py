"""Dynamic Resource Allocation (DRA) API objects — structured parameters.

Scheduling-relevant slices of resource.k8s.io/v1 (reference:
staging/src/k8s.io/dynamic-resource-allocation, 33.1k LoC;
plugins/dynamicresources/ 2152 LoC core): ResourceSlice publishes a node's
devices, ResourceClaim requests devices by class/selector, DeviceClass names
a device category. The reference's CEL device selectors are expressed here as
attribute equality maps (the dominant production shape); CEL itself is out of
scope for the scheduler's hot path.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

from .types import _next_uid


@dataclass
class Device:
    name: str
    attributes: Dict[str, str] = field(default_factory=dict)
    capacity: Dict[str, str] = field(default_factory=dict)
    # Node-allocatable resources this device CONSUMES when allocated
    # (nodeallocatabledynamicresources.go: DRA allocations that draw from
    # the node's cpu/memory budget), e.g. {"cpu": "2", "memory": "4Gi"}.
    consumes: Dict[str, str] = field(default_factory=dict)


@dataclass
class ResourceSlice:
    """resource.k8s.io ResourceSlice: one node's devices for one driver."""

    node_name: str
    driver: str
    devices: List[Device] = field(default_factory=list)


@dataclass
class DeviceClass:
    """DeviceClass: a named device category; `selectors` are attribute
    equality requirements every matching device must satisfy.
    `extended_resource_name` maps a v1 extended resource (e.g.
    example.com/gpu) onto this class: pods requesting it are satisfied via
    DRA when no device plugin advertises it
    (resource/v1 types.go:2427 ExtendedResourceName +
    extendeddynamicresources.go)."""

    name: str
    selectors: Dict[str, str] = field(default_factory=dict)
    extended_resource_name: str = ""


@dataclass
class DeviceRequest:
    """One request inside a claim (spec.devices.requests[*])."""

    name: str = "req"
    device_class: str = ""
    count: int = 1
    selectors: Dict[str, str] = field(default_factory=dict)
    # CEL-equivalent device selector (compile_device_expression below);
    # evaluated per candidate device in addition to the equality selectors.
    expression: str = ""


@dataclass
class AllocatedDevice:
    driver: str
    device: str

    def key(self) -> Tuple[str, str]:
        return (self.driver, self.device)


@dataclass
class ResourceClaim:
    name: str = ""
    namespace: str = "default"
    uid: str = ""
    requests: List[DeviceRequest] = field(default_factory=list)
    # status
    allocated_node: str = ""                      # "" = unallocated
    allocations: List[AllocatedDevice] = field(default_factory=list)
    reserved_for: List[str] = field(default_factory=list)  # pod uids

    def __post_init__(self):
        if not self.uid:
            self.uid = _next_uid("claim")

    @property
    def key(self) -> str:
        return f"{self.namespace}/{self.name}"

    @property
    def allocated(self) -> bool:
        return bool(self.allocated_node)


# ---------------------------------------------------------------------------
# Device selection expressions — the structured-parameters CEL equivalent
# (staging dynamic-resource-allocation/cel; resource.k8s.io DeviceSelector
# `cel.expression`). A restricted Python-syntax expression evaluated per
# device with the same surface the reference exposes:
#
#     device.attributes["gpu.example.com/model"] == "a100"
#     device.capacity["memory"] >= 40 and device.driver == "gpu.example.com"
#
# The AST is validated against a whitelist (comparisons, boolean logic,
# arithmetic, subscripts on device.attributes/capacity, literals) — no
# calls, no imports, no dunder access. Parse once per request, evaluate per
# device (the reference compiles CEL programs the same way).
# ---------------------------------------------------------------------------

import ast as _ast

_ALLOWED_NODES = (
    _ast.Expression, _ast.BoolOp, _ast.And, _ast.Or, _ast.UnaryOp, _ast.Not,
    _ast.USub, _ast.Compare, _ast.Eq, _ast.NotEq, _ast.Lt, _ast.LtE, _ast.Gt,
    _ast.GtE, _ast.In, _ast.NotIn, _ast.BinOp, _ast.Add, _ast.Sub, _ast.Mult,
    _ast.Div, _ast.Mod, _ast.Constant, _ast.Name, _ast.Load, _ast.Attribute,
    _ast.Subscript, _ast.Index, _ast.Tuple, _ast.List,
)


class ExpressionError(ValueError):
    """Invalid or disallowed device selector expression."""


class _ConstCoercer(_ast.NodeTransformer):
    """Coerce quantity-shaped string literals ONCE at compile time (the
    reference's CEL environment types quantity constants the same way):
    `"40Gi"` in a comparison against `device.attributes[...]` /
    `device.capacity[...]` becomes the coerced numeric bound to an injected
    name, so runtime comparisons are plain int/float ops against the (also
    coerced) map values — the coerced value classes need no cross-type
    string equality, keeping their __eq__ consistent with their int/float
    __hash__ (regression in tests/test_dra.py
    test_quantity_hash_eq_consistency).

    Scope: ONLY direct comparator operands (and their tuple/list members,
    for `in`) of a Compare that involves one of the two quantity maps.
    Subscript KEYS (`device.attributes["8"]` looks up the string key) and
    comparisons against the plain-string fields (`device.name == "0"`)
    keep their literal strings. Known edge: a CHAINED comparison mixing a
    string field and a quantity map (`device.name == "8" ==
    device.attributes["c"]`) treats its string literals as quantities —
    CEL has no comparison chaining, so the quantity reading wins. Runs
    AFTER validation, so injected names cannot collide with user
    identifiers (only `device` is legal)."""

    def __init__(self):
        self.bindings = {}

    @staticmethod
    def _qty_map_operand(n) -> bool:
        # device.attributes[...] / device.capacity[...] — the maps whose
        # VALUES are quantity-coerced (_CoercingMap).
        return (isinstance(n, _ast.Subscript)
                and isinstance(n.value, _ast.Attribute)
                and n.value.attr in ("attributes", "capacity"))

    def _coerce_const(self, node):
        if isinstance(node, _ast.Constant) and isinstance(node.value, str):
            coerced = _CoercingMap._coerce(node.value)
            if not isinstance(coerced, str):
                name = f"_qty{len(self.bindings)}"
                self.bindings[name] = coerced
                return _ast.copy_location(
                    _ast.Name(id=name, ctx=_ast.Load()), node)
        elif isinstance(node, (_ast.Tuple, _ast.List)):
            node.elts = [self._coerce_const(e) for e in node.elts]
        return node

    def visit_Compare(self, node):
        self.generic_visit(node)  # nested compares inside operands first
        operands = [node.left] + list(node.comparators)
        if any(self._qty_map_operand(o) for o in operands):
            node.left = self._coerce_const(node.left)
            node.comparators = [self._coerce_const(c)
                                for c in node.comparators]
        return node


def compile_device_expression(expr: str):
    """Validate + compile a device selector expression. Returns a callable
    (device, driver) -> bool. Raises ExpressionError on disallowed syntax."""
    try:
        tree = _ast.parse(expr, mode="eval")
    except SyntaxError as e:
        raise ExpressionError(f"invalid expression: {e}") from e
    for node in _ast.walk(tree):
        if not isinstance(node, _ALLOWED_NODES):
            raise ExpressionError(
                f"disallowed syntax {type(node).__name__!r} in device expression")
        if isinstance(node, _ast.Name) and node.id != "device":
            raise ExpressionError(f"unknown identifier {node.id!r}")
        if isinstance(node, _ast.Attribute):
            if node.attr.startswith("__") or node.attr not in (
                    "attributes", "capacity", "driver", "name"):
                raise ExpressionError(f"unknown device field {node.attr!r}")
    coercer = _ConstCoercer()
    tree = _ast.fix_missing_locations(coercer.visit(tree))
    qty_consts = coercer.bindings
    code = compile(tree, "<device-selector>", "eval")

    class _DeviceView:
        __slots__ = ("attributes", "capacity", "driver", "name")

        def __init__(self, device, driver):
            # Coerced maps are memoized ON the device (the exception-driven
            # coercion chain costs more than the whole match when it runs
            # per evaluation), validated against the raw dicts' identities:
            # a slice update that REPLACES the attribute/capacity maps (the
            # supported mutation shape — spec maps are copy-on-write, never
            # edited in place) invalidates the memo automatically.
            raw_cap = getattr(device, "capacity", None)
            memo = device.__dict__.get("_coerced_memo")
            if (memo is None or memo[0] is not device.attributes
                    or memo[1] is not raw_cap):
                memo = device._coerced_memo = (
                    device.attributes, raw_cap,
                    _CoercingMap.coerced(device.attributes),
                    _CoercingMap.coerced(raw_cap or {}))
            self.attributes = memo[2]
            self.capacity = memo[3]
            self.driver = driver
            self.name = device.name

    def matcher(device, driver="") -> bool:
        try:
            env = {"device": _DeviceView(device, driver)}
            if qty_consts:
                env.update(qty_consts)
            return bool(eval(code, {"__builtins__": {}}, env))  # noqa: S307 - AST-whitelisted
        except Exception:
            # CEL runtime errors make the device non-matching (the reference
            # treats evaluation errors as "does not satisfy selector").
            return False

    return matcher


class _CoercingMap(dict):
    """Attribute/capacity map that compares numerically when both sides are
    numeric, with full QUANTITY semantics for suffixed strings — the typed
    CEL surface: device.capacity["memory"] >= 40 * 1024**3 holds for
    "40Gi" (apimachinery resource.Quantity comparisons in the reference's
    CEL environment)."""

    @classmethod
    def coerced(cls, raw: Dict[str, str]) -> "_CoercingMap":
        """Pre-coerce every value ONCE (the maps are per-device spec)."""
        out = cls()
        for k, v in raw.items():
            out[k] = cls._coerce(v)
        return out

    @staticmethod
    def _coerce(v):
        if isinstance(v, str):
            try:
                return _QtyInt(int(v))
            except ValueError:
                pass
            try:
                return _QtyFloat(float(v))
            except ValueError:
                pass
            try:
                from .resource import parse_quantity
                q = parse_quantity(v)
                iq = int(q)
                return _QtyInt(iq) if q == iq else _QtyFloat(float(q))
            except Exception:
                return v
        return v

    def __getitem__(self, key):
        return dict.get(self, key)


class _QtyMixin:
    """Coerced quantity values: EQUALITY is strictly numeric (inherited
    int/float __eq__/__hash__ — equal objects hash equal, so coerced values
    are safe set members / dict keys next to any other form). The CEL
    surface still holds — device.capacity["mem"] == "40Gi" and
    == 40*1024**3 are both True —
    because expression string LITERALS are coerced once at compile time
    (_ConstCoercer) and the map values once per device (_CoercingMap), so
    both sides of every runtime comparison are already numeric. ORDERING
    operands keep the string coercion (`qty >= "32Gi"` for direct API
    users); ordering carries no hash contract."""

    __slots__ = ()

    def _other(self, other):
        if isinstance(other, str):
            return _CoercingMap._coerce(other)
        return other

    def __lt__(self, other):
        return super().__lt__(self._other(other))

    def __le__(self, other):
        return super().__le__(self._other(other))

    def __gt__(self, other):
        return super().__gt__(self._other(other))

    def __ge__(self, other):
        return super().__ge__(self._other(other))


class _QtyInt(_QtyMixin, int):
    pass


class _QtyFloat(_QtyMixin, float):
    pass
