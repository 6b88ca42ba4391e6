"""Arithmetic on other metrics of the same run and on the cell's numbers.

args: ``expr``, an expression of ``+ - * /``, parentheses, numbers and
names. A name is another metric (``fused_program_ms``), a path into the
cell's data (``train_args.sgd_steps_per_chunk``) or one of ``run.names``
(``flops.train_window``, ``peak.bf16_flops_per_s``, ``chips``). A derived
metric is named as such in PERF.md. If a name has no value the metric is
left out."""

import ast
import operator

OPS = {ast.Add: operator.add, ast.Sub: operator.sub,
       ast.Mult: operator.mul, ast.Div: operator.truediv}


class Missing(Exception):
    pass


def _name(node):
    if isinstance(node, ast.Name):
        return node.id
    if isinstance(node, ast.Attribute):
        return _name(node.value) + '.' + node.attr
    raise ValueError('not a name')


def _lookup(run, name):
    if name in run.values:
        return run.values[name]
    if name in run.names:
        return run.names[name]
    try:
        return float(run.param(name))
    except (KeyError, TypeError, ValueError):
        raise Missing(name)


def _eval(run, node):
    if isinstance(node, ast.Expression):
        return _eval(run, node.body)
    if isinstance(node, ast.Constant) and isinstance(node.value, (int, float)):
        return node.value
    if isinstance(node, ast.BinOp) and type(node.op) in OPS:
        return OPS[type(node.op)](_eval(run, node.left),
                                  _eval(run, node.right))
    if isinstance(node, ast.UnaryOp) and isinstance(node.op, ast.USub):
        return -_eval(run, node.operand)
    if isinstance(node, (ast.Name, ast.Attribute)):
        return _lookup(run, _name(node))
    raise ValueError('derived: %s is not allowed in an expression'
                     % type(node).__name__)


def read(run, expr):
    try:
        return _eval(run, ast.parse(expr, mode='eval'))
    except (Missing, ZeroDivisionError):
        return None
