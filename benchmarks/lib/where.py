"""A GraphQL-grammar `where` read in numpy: the plain reference's reading of
a filter. Shares no code with weaviate_tpu/: the grammar is the public one
(`operator`, `path`, `operands`, `valueInt`), the evaluation is a comparison
of columns.

A column is what a dataset says its rows carry under a property's name: an
int array `[R]` (a scalar property) or `[R, T]` (a bag: an `int[]` property,
padded with -1; a row matches a value if any of its entries equals it, which
is how the inverted index of the program reads `Equal` on an array property
and how Weaviate documents it).
"""

from __future__ import annotations

import numpy as np

_COMPARE = {
    "Equal": np.equal, "NotEqual": np.not_equal,
    "LessThan": np.less, "LessThanEqual": np.less_equal,
    "GreaterThan": np.greater, "GreaterThanEqual": np.greater_equal,
}


class WhereError(ValueError):
    pass


def _value(clause: dict):
    if "valueInt" not in clause:
        raise WhereError(f"only valueInt is read here: {clause}")
    return clause["valueInt"]


def _holds(col: np.ndarray, value: int) -> np.ndarray:
    if col.ndim == 1:
        return col == int(value)
    return ((col == int(value)) & (col >= 0)).any(1)    # padding is no entry


def evaluate(where: dict, columns: dict) -> np.ndarray:
    """-> bool [R]: the rows of `columns` that `where` allows."""
    op = where.get("operator")
    if op in ("And", "Or"):
        parts = [evaluate(w, columns) for w in where.get("operands") or ()]
        if not parts:
            raise WhereError(f"{op} without operands")
        return np.logical_and.reduce(parts) if op == "And" \
            else np.logical_or.reduce(parts)
    if op == "Not":
        (only,) = where["operands"]
        return ~evaluate(only, columns)
    path = where.get("path") or ()
    if len(path) != 1 or path[0] not in columns:
        raise WhereError(f"path {path!r}: the dataset's rows carry "
                         f"{sorted(columns)}")
    col = np.asarray(columns[path[0]])
    if op in ("ContainsAny", "ContainsAll"):
        values = _value(where)
        hits = [_holds(col, v) for v in
                (values if isinstance(values, list) else [values])]
        return np.logical_or.reduce(hits) if op == "ContainsAny" \
            else np.logical_and.reduce(hits)
    if op == "Equal":
        return _holds(col, _value(where))
    if op in _COMPARE and col.ndim == 1:
        return _COMPARE[op](col, int(_value(where)))
    raise WhereError(f"operator {op!r} on a column of {col.ndim} dimensions")


def allowed(wheres: list, columns: dict, n_rows: int) -> np.ndarray:
    """-> bool [len(wheres), n_rows]; a `where` of None allows every row."""
    out = np.ones((len(wheres), n_rows), bool)
    for i, where in enumerate(wheres):
        if where is not None:
            out[i] = evaluate(where, columns)
    return out


def properties(columns: dict, n_rows: int) -> list[dict]:
    """The rows' properties as the write path takes them: a scalar column's
    entry as an int, a bag's as the list of its entries that are not padding."""
    cols = {name: np.asarray(col) for name, col in columns.items()}
    return [{name: (int(col[i]) if col.ndim == 1
                    else [int(v) for v in col[i] if v >= 0])
             for name, col in cols.items()} for i in range(n_rows)]
