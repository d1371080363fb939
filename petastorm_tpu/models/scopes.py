"""Which part of the model and which pass every instruction of a compiled
train step belongs to, read from the scopes the instructions carry.

Flax wraps every module call in a ``jax.named_scope`` and the models add one
where work runs in no module, so an instruction's ``op_name`` in the
optimized HLO is its module path under jax's wrappers:
``jit(train_step)/transpose(jvp(LM))/checkpoint/rematted_computation/
block_3/mixer/kda/dot_general``. :func:`parse_hlo_scopes` turns a module's
text into ``instruction -> part, pass, path``; the parts (:data:`PARTS`) and
the ordered rules that say which scope names make which part
(:data:`PART_RULES`) are declared here, once, beside the models whose names
they hold, so that an operator's profile and the benchmark agree and a new
model adds a rule in this file and nowhere else. ``petastorm_tpu.trace``'s
:class:`~petastorm_tpu.trace.StepProgram` calls this when
``Tracer.op_scopes()`` is asked; no jax is needed to parse a text.
"""

import functools
import re

#: What a device operation of a train step belongs to. ``collective`` goes by
#: the opcode whatever the scope, ``unscoped`` is an instruction the compiler
#: made with no ``op_name`` (layout copies, prefetches), ``other`` one whose
#: scope no rule of :data:`PART_RULES` knows.
PARTS = ('embed', 'mixer', 'ffn.dense', 'ffn.routed', 'ffn.shared', 'streams',
         'norm', 'body', 'head', 'loss', 'optimizer', 'collective', 'other',
         'unscoped')

#: ``(pattern, part)``, in order, searched among an instruction's scope names
#: (flax module names and ``jax.named_scope``s of its ``op_name``, jax's
#: wrappers stripped, the primitive left out): the first that matches says
#: the part. Declared here, once, so that an operator's profile and the
#: benchmark agree. A sub-layer's names come before what holds it (a block's
#: ``moe`` inside a ``ffn_hc`` or an ``mtp_0`` is routed experts), and a new
#: model adds a rule, not a part.
PART_RULES = tuple((re.compile(r'(?:^|/)(?:' + pattern + r')(?:/|$)'), part)
                   for pattern, part in (
    ('optimizer', 'optimizer'),
    ('loss', 'loss'),
    ('shared', 'ffn.shared'),
    ('moe|router|routing|dispatch|token_sums', 'ffn.routed'),
    ('mixer|attn|mixer_norm|attn_norm|gdn|kda|kda_exact|ssd', 'mixer'),
    ('mlp|mlp_norm', 'ffn.dense'),
    (r'\w+_hc|hc|streams', 'streams'),
    (r'embed|pos_embed|Embed_\d+', 'embed'),
    (r'head|final_norm|mtp_\d+', 'head'),
    (r'BatchNorm_\d+|LayerNorm_\d+|bn_init|norm_proj|\w*norm', 'norm'),
    (r'stem|conv_init|conv_proj|Conv_\d+|\w+Block_\d+', 'body'),
))

_COLLECTIVES = ('all-reduce', 'all-gather', 'reduce-scatter',
                'collective-permute', 'all-to-all', 'collective-broadcast')
#: Opcodes whose time covers their called computations' instructions: a
#: reader leaves them out and counts what they run once.
CONTAINERS = ('conditional', 'while', 'call')
# Never a device event of their own: kept out of the table.
_NO_EVENT = ('parameter', 'constant', 'tuple', 'get-tuple-element', 'bitcast',
             'after-all', 'partition-id', 'replica-id')
# Names jax's own transformations and control flow put on the name stack.
_WRAPPER = re.compile(r'^(?:checkpoint|rematted_computation|pjit|closed_call|'
                      r'custom_[jv][vj]p_call\w*|shard_map|while|body|cond|'
                      r'branch_\d+\w*)$')
_COMPUTATION = re.compile(r'^(ENTRY\s+)?%?([^\s(]+)\s*\(.*\)\s*->\s*.*\{\s*$')
_INSTRUCTION = re.compile(r'^\s+(?:ROOT\s+)?%?([^\s=]+) = ')
_OPCODE = re.compile(r'\s*([a-z][\w\-]*)\(')
_RESULT = re.compile(r'\(*([a-z0-9]+)\[([0-9,]*)\]')
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_CALLEE = re.compile(r'\b(calls|body|condition|to_apply|true_computation|'
                     r'false_computation)=%?([^\s,)}]+)')
_BRANCHES = re.compile(r'\bbranch_computations=\{([^}]*)\}')


def _split_scopes(op_name):
    """``op_name`` at its slashes outside parentheses."""
    parts, depth, start = [], 0, 0
    for i, c in enumerate(op_name):
        if c == '(':
            depth += 1
        elif c == ')':
            depth -= 1
        elif c == '/' and depth == 0:
            parts.append(op_name[start:i])
            start = i + 1
    parts.append(op_name[start:])
    return parts


def scope_of(op_name):
    """``(scopes, primitive, pass)`` of an HLO ``op_name``: the scope names
    (flax module names and ``jax.named_scope``s) with jax's wrappers
    stripped (``jit(f)`` and ``pjit`` go with what they name; ``jvp(x)``,
    ``transpose(x)``, ``vmap(x)`` leave ``x``; ``checkpoint``, ``cond``,
    ``branch_1_fun``, ``while``, ``body``, ``custom_vjp_call`` go), the
    primitive the instruction came from (the last name, ``''`` where the
    last was a wrapper), and the pass: ``recompute`` under
    ``rematted_computation``, else ``backward`` under ``transpose(``, else
    ``forward``."""
    names, backward, recompute, last = [], False, False, False
    for scope in _split_scopes(op_name):
        while True:
            head, paren, rest = scope.partition('(')
            if not paren or not scope.endswith(')'):
                break
            if head == 'transpose':
                backward = True
            elif head in ('jit', 'pjit'):
                scope = ''
                break
            scope = rest[:-1]
        if scope == 'rematted_computation':
            recompute = True
        last = bool(scope) and not _WRAPPER.match(scope)
        if last and (not names or names[-1] != scope):
            names.append(scope)
    primitive = names.pop() if last and names else ''
    return names, primitive, ('recompute' if recompute else
                              'backward' if backward else 'forward')


def part_of(scopes):
    """The part of :data:`PARTS` that scope names (a list, or joined by
    ``/``) belong to by :data:`PART_RULES`."""
    path = scopes if isinstance(scopes, str) else '/'.join(scopes)
    for pattern, part in PART_RULES:
        if pattern.search(path):
            return part
    return 'other'


_ARGUMENT_KEY = re.compile(r"\['([^']+)'\]")


def _scoped(opcode, op_name):
    """``(part, pass, path)`` of one instruction; the path is its scope
    names and its primitive. A name with no slash is none of the program's
    scopes: a primitive's alone (the compiler's expansions: unscoped), or
    an argument's (``state.params['block_0']['mixer']['conv_k']``, a layout
    copy of that leaf: the part of the leaf's modules, in no pass)."""
    collective = opcode.startswith(_COLLECTIVES)
    if op_name and '/' not in op_name:
        keys = _ARGUMENT_KEY.findall(op_name)
        if not keys or collective:
            op_name = None
        else:
            if op_name.startswith('state.opt_state'):
                keys.insert(0, 'optimizer')
            part = part_of(keys)
            return part, 'update' if part == 'optimizer' else None, \
                '/'.join(keys)
    if not op_name:
        return 'collective' if collective else 'unscoped', None, ''
    scopes, primitive, which = scope_of(op_name)
    path = '/'.join(scopes + [primitive] if primitive else scopes)
    if collective:
        return 'collective', which, path
    part = part_of(scopes)
    return part, 'update' if part == 'optimizer' else which, path


def parse_hlo_scopes(text):
    """``{'module': name, 'instructions': {name: {'opcode', 'result', 'part',
    'pass', 'path', 'parts_fused'}}}`` of an optimized HLO module's text: the
    instructions of the entry computation and of what its loops, branches
    and calls run (what a device trace can hold as events), a fusion under
    its own scope (its root's where it has none) with the distinct parts of
    what it fused."""
    module, computations, entry, current = None, {}, None, None
    for line in text.splitlines():
        if current is None:
            if line.startswith('HloModule'):
                module = line.split()[1].rstrip(',')
                continue
            m = _COMPUTATION.match(line)
            if m:
                current = computations[m.group(2)] = []
                if m.group(1):
                    entry = m.group(2)
            continue
        if line.startswith('}'):
            current = None
            continue
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        at = m.end()
        if line[at] == '(':                     # a tuple's type: to its end
            depth = 0
            for at in range(at, len(line)):
                depth += (line[at] == '(') - (line[at] == ')')
                if depth == 0:
                    break
            at += 1
        else:
            at = line.find(' ', at)
        op = _OPCODE.match(line, at)
        if not op:
            continue
        result = _RESULT.match(line, m.end())
        name = _OP_NAME.search(line, op.end())
        current.append((
            m.group(1), op.group(1),
            result and '{}[{}]'.format(*result.groups()),
            name and name.group(1).replace('\\', ''), line[op.end():]))
    instructions = {}
    # A layer's instructions share names; the memo goes with this call.
    scoped_of = functools.lru_cache(maxsize=None)(_scoped)

    def fused_parts(computation):
        """The parts of a fused computation's instructions, and the scope
        of its root (of the last instruction that has one, where the root
        is a tuple or a bitcast the compiler made)."""
        parts, last = set(), None
        for _, opcode, _, op_name, _ in computations.get(computation, ()):
            scoped = scoped_of(opcode, op_name)
            if scoped[0] != 'unscoped' and opcode != 'parameter':
                last = scoped
                parts.add(last[0])
        return parts, last

    # (computation, the scope of the loop, branch or call that runs it: what
    # its instructions with no ``op_name`` of their own belong to)
    seen, queue = set(), [(entry, None)]
    while queue:
        computation, inherited = queue.pop()
        if computation in seen or computation not in computations:
            continue
        seen.add(computation)
        for name, opcode, result, op_name, rest in computations[computation]:
            callees = dict(_CALLEE.findall(rest))
            parts_fused = None
            scoped = scoped_of(opcode, op_name)
            if opcode == 'fusion':
                parts_fused, root = fused_parts(callees.get('calls'))
                parts_fused = sorted(parts_fused)
                if not op_name and root is not None:
                    scoped = root
            if scoped[0] == 'unscoped' and inherited is not None:
                scoped = inherited
            if opcode in CONTAINERS or opcode.startswith('async'):
                within = None if scoped[0] == 'unscoped' else scoped
                queue.extend((callee, within) for callee in callees.values())
                branches = _BRANCHES.search(rest)
                if branches:
                    queue.extend((b.strip().lstrip('%'), within) for b in
                                 branches.group(1).split(','))
            if opcode in _NO_EVENT:
                continue
            instructions[name] = {'opcode': opcode, 'result': result,
                                  'part': scoped[0], 'pass': scoped[1],
                                  'path': scoped[2], 'parts_fused': parts_fused}
    return {'module': module, 'instructions': instructions}
