"""Arithmetic expressions as aggregate inputs: `sum(l_extendedprice*l_discount)`.

An aggregate's input is a column or a tree over columns and integer / decimal
literals with `*`, `+`, `-` (TPC-H Q6's revenue product; no division). This
module owns the text form both ways — `parse` (a typed ValueError for
anything outside the grammar, which protocol.py renders as a 400) and
`render` (the canonical text that keys the result: no spaces, the fewest
parentheses that keep the tree) — and the host evaluation, which is nothing
but pyarrow.compute applied node by node: result type, decimal precision and
scale, null propagation and integer wraparound are Arrow's by construction.
The device lane (serve/query_device.py) types its integer program from the
same evaluation over EMPTY arrays, so both lanes agree on every type without
a second rule book.

ONE rule is this module's own, for a product Arrow cannot type. Arrow gives
decimal(p1, s1) * decimal(p2, s2) the type decimal(p1 + p2 + 1, s1 + s2) and
refuses it past decimal128's 38 digits — TPC-H Q1's charge,
l_extendedprice*(1-l_discount)*(1+l_tax), asks for 49 with decimal literals
and 61 with integer ones. Such a `*` node is typed decimal128(38, s1 + s2),
the cap Spark and DuckDB apply, and computed EXACTLY OR NOT AT ALL: the
operand of the greater precision (the left one on a tie; an integer operand
counts as the decimal Arrow would make of it, int64 as decimal(19, 0)) is
first cast, CHECKED, down to the precision that makes the product's declared
precision 38 (`capped_product`: decimal(32, 4) * decimal(16, 2) multiplies as
decimal(21, 4) * decimal(16, 2) = decimal(38, 6)). A value that does not fit
the narrower precision raises (the request's typed 400): no node is ever
rounded, truncated or computed in float. The device lane proves the same
bound from the chunks' statistics before it runs (query_device._bind) and
leaves the unit to the host where it cannot.

    expr   := term (("+" | "-") term)*
    term   := factor ("*" factor)*
    factor := NUMBER | NAME | "`" any name "`" | "(" expr ")"

A tree is nested tuples, hashable (the device kernel takes its integer twin
as a static argument): ("col", name) | ("lit", text) | (op, left, right).
A literal without a fraction is an Arrow int64, one with a fraction a
decimal128 of exactly its digits ("0.05" is decimal128(3, 2)). A column
whose name holds an operator character goes in backticks.
"""

from __future__ import annotations

import decimal
import re

__all__ = ["parse", "render", "columns", "evaluate", "literal", "capped_product"]

MAX_PRECISION = 38  # decimal128's

MAX_NODES = 64  # a tree is query text, not a program: bounded like max_groups

_TOKEN = re.compile(
    r"\s*(?:(?P<num>\d+(?:\.\d+)?)|(?P<name>[A-Za-z_][A-Za-z0-9_.]*)"
    r"|`(?P<quoted>[^`]+)`|(?P<op>[*+\-()]))"
)
_PLAIN_NAME = re.compile(r"[A-Za-z_][A-Za-z0-9_.]*\Z")
_PRECEDENCE = {"+": 1, "-": 1, "*": 2}


def parse(text: str):
    """The tree of an expression text; ValueError outside the grammar."""
    tokens, pos = [], 0
    end = len(text.rstrip())
    while pos < end:
        m = _TOKEN.match(text, pos)
        if m is None:
            at = text[pos:].lstrip()[:1]
            hint = " (division is not supported)" if at == "/" else ""
            raise ValueError(f"unexpected {at!r} in expression {text!r}{hint}")
        tokens.append((m.lastgroup, m.group(m.lastgroup)))
        pos = m.end()
    if len(tokens) > 2 * MAX_NODES:
        raise ValueError(f"expression {text!r} is too long")
    tree, rest = _expr(tokens, 0, text)
    if rest != len(tokens):
        raise ValueError(f"unexpected {tokens[rest][1]!r} in expression {text!r}")
    if not columns(tree):
        raise ValueError(f"expression {text!r} names no column")
    return tree


def _expr(tokens, i, text, level=1):
    """Precedence climbing over the two levels of _PRECEDENCE."""
    if level > 2:
        return _factor(tokens, i, text)
    left, i = _expr(tokens, i, text, level + 1)
    while i < len(tokens) and tokens[i][0] == "op" and _PRECEDENCE.get(tokens[i][1]) == level:
        right, j = _expr(tokens, i + 1, text, level + 1)
        left, i = (tokens[i][1], left, right), j
    return left, i


def _factor(tokens, i, text):
    if i >= len(tokens):
        raise ValueError(f"expression {text!r} ends where a column or a number should stand")
    kind, tok = tokens[i]
    if kind == "num":
        if len(tok) > 18:  # an int64, or a decimal well inside decimal128's 38 digits
            raise ValueError(f"literal {tok} in expression {text!r} has more than 18 characters")
        return ("lit", tok), i + 1
    if kind in ("name", "quoted"):
        return ("col", tok), i + 1
    if tok == "(":
        tree, i = _expr(tokens, i + 1, text)
        if i >= len(tokens) or tokens[i][1] != ")":
            raise ValueError(f"unbalanced parenthesis in expression {text!r}")
        return tree, i + 1
    raise ValueError(f"unexpected {tok!r} in expression {text!r}")


def render(tree) -> str:
    """The canonical text: parse(render(t)) == t."""
    if tree[0] == "col":
        return tree[1] if _PLAIN_NAME.match(tree[1]) else f"`{tree[1]}`"
    if tree[0] == "lit":
        return tree[1]
    op, left, right = tree
    mine = _PRECEDENCE[op]

    def side(t, keep_equal):
        inner = _PRECEDENCE.get(t[0], 3)
        s = render(t)
        return s if inner > mine or (keep_equal and inner == mine) else f"({s})"

    return f"{side(left, True)}{op}{side(right, False)}"


def columns(tree) -> list:
    """The column names of a tree, in order of first appearance."""
    if tree[0] == "col":
        return [tree[1]]
    if tree[0] == "lit":
        return []
    out = columns(tree[1])
    return out + [c for c in columns(tree[2]) if c not in out]


def literal(text: str):
    """A literal's Python value: int without a fraction, else Decimal."""
    return decimal.Decimal(text) if "." in text else int(text)


def evaluate(tree, column):
    """The tree over Arrow data: `column(name)` gives each column (an array,
    a chunked array, or an empty array when only the type is wanted);
    literals are Arrow scalars; the operators are pyarrow.compute's unchecked
    multiply / add / subtract. Raises what pyarrow raises."""
    import pyarrow as pa
    import pyarrow.compute as pc

    if tree[0] == "col":
        return column(tree[1])
    if tree[0] == "lit":
        return pa.scalar(literal(tree[1]))
    fn = {"*": pc.multiply, "+": pc.add, "-": pc.subtract}[tree[0]]
    left, right = evaluate(tree[1], column), evaluate(tree[2], column)
    if tree[0] == "*":
        cap = capped_product(left.type, right.type)
        if cap is not None:  # checked: a value past the narrower precision raises
            side, narrower = cap
            if side == 0:
                left = left.cast(narrower)
            else:
                right = right.cast(narrower)
    return fn(left, right)


def _as_decimal(typ):
    """The decimal128 type Arrow's arithmetic makes of an operand's type
    (itself; an integer's by Arrow's own implicit cast, read off a product
    with decimal(1, 0), whose precision is the operand's + 2); None for any
    other type."""
    import pyarrow as pa
    import pyarrow.compute as pc

    if pa.types.is_decimal128(typ):
        return typ
    if not pa.types.is_integer(typ):
        return None
    probe = pc.multiply(pa.array([], typ), pa.array([], pa.decimal128(1, 0))).type
    return pa.decimal128(probe.precision - 2, 0)


def capped_product(left_type, right_type):
    """The module's one typing rule (docstring): None where Arrow types the
    product itself, else (side, type) — the operand to narrow (0 left, 1
    right) and the decimal type to cast it to, checked, so that the product
    is decimal128(38, s_left + s_right). Raises ArrowInvalid where no
    precision of that operand gives a product of 38 digits."""
    import pyarrow as pa

    lt, rt = _as_decimal(left_type), _as_decimal(right_type)
    if lt is None or rt is None or not (
        pa.types.is_decimal(left_type) or pa.types.is_decimal(right_type)
    ):
        return None  # no decimal product: integers wrap, floats are Arrow's
    if lt.precision + rt.precision + 1 <= MAX_PRECISION:
        return None
    side = 0 if lt.precision >= rt.precision else 1
    wide, other = (lt, rt) if side == 0 else (rt, lt)
    precision = MAX_PRECISION - 1 - other.precision
    if precision < max(wide.scale, 1):
        raise pa.ArrowInvalid(
            f"Decimal precision out of range [1, {MAX_PRECISION}]: a product of "
            f"{lt} and {rt} has no exact decimal128 type"
        )
    return side, pa.decimal128(precision, wide.scale)
