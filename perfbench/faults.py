"""Faults a test plants under the timed path, to see ``correct`` come out
false. Never on in a benchmark run: ``--fault`` is for ``tests/``.

``state_unchanged``  the step returns the state it was given.
``half_batch``       half of the batch left out, the mean taken over the rest
                     (the rows of the first half stand twice).
``no_exchange``      the exchange between chips left out: every chip works on
                     the first chip's rows, as a replica that never hears of
                     the others' gradients would.
``row_altered``      one byte of one row altered where the loader hands it over.
"""

NAMES = ('state_unchanged', 'half_batch', 'no_exchange', 'row_altered')


class Fault(object):
    def __init__(self, name, batch, mesh):
        if name is not None and name not in NAMES:
            raise SystemExit('unknown fault {!r}'.format(name))
        self.name = name
        self.batch = batch
        self.chips = 1 if mesh is None else mesh.devices.size

    def wrap_step(self, step):
        if self.name == 'state_unchanged':
            import jax
            import jax.numpy as jnp

            def unchanged(state, batch):
                kept = jax.tree_util.tree_map(jnp.copy, state)
                _, metrics = step(state, batch)
                return kept, metrics
            return unchanged
        if self.name in ('half_batch', 'no_exchange'):
            import jax.numpy as jnp
            keep = self.batch // (2 if self.name == 'half_batch'
                                  else self.chips)
            copies = self.batch // keep

            def repeat(a):
                return jnp.concatenate([a[:keep]] * copies, axis=0)

            def partial(state, batch):
                return step(state, type(batch)(*[repeat(a) for a in batch]))
            return partial
        return step

    def wrap_batch(self, batch):
        if self.name != 'row_altered':
            return batch
        fields = batch._asdict()
        name = [k for k in fields if k not in ('id', 'label')][0]
        data = fields[name]
        flat = data.reshape(data.shape[0], -1)
        fields[name] = flat.at[0, 0].set(flat[0, 0] ^ 1).reshape(data.shape)
        return type(batch)(**fields)
