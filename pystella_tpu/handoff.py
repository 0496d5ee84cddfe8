"""The Laplacian the loop's energy has just taken, handed to the stage
program about to take it again.

The reference-style driver loop takes ``derivs.lap(state["f"])`` for the
energy between two RK stages, and the next stage program's right-hand
side takes ``derivs.lap`` of that same array. Where a Laplacian is a
transform pair (:class:`~pystella_tpu.fourier.SpectralCollocator`) that is
half of the step done twice. Two parties speak through this module, and
neither imports the other:

- the **collocator** owns one :class:`LastLaplacian`. After an eager
  ``lap`` it :meth:`~LastLaplacian.remember`\\ s one pair, the array it
  was given and the Laplacian it returned, replacing the pair before; at
  the start of its next eager call of any kind it
  :meth:`~LastLaplacian.forget`\\ s, before that call allocates. Under
  trace it asks :meth:`~LastLaplacian.offered` whether the tracer it was
  given is the one whose Laplacian is on offer, and returns that instead
  of building the transforms.
- the **generic stepper's per-stage dispatch** asks :func:`take` whether
  any leaf it is about to pass **is** a remembered array. If so it gets a
  :class:`HandedIn` to pass as one more, donated, argument of its
  program, and traces that program's body under :func:`offer`.

A hit is exact by construction: the very array object in (Python
identity, not deleted), so what the eager program returned is what the
traced one would compute again; equal values in another buffer are a
miss. Only what the code can observe in its input decides: there is no
switch. The pair lives from the eager call to the stage dispatch that is
passed its array (:func:`take` forgets it, hit or miss), to the
collocator's next eager call, or to the end of the array it was made
from, whichever comes first; collocators are held weakly, and one that
is collected takes its pair with it.

**Only a Laplacian nobody holds any more is handed in, and it is
consumed.** The stage program gets it as a donated argument: an output
takes its buffer, so the program holds what the one that transforms for
itself holds and not one lattice array more (at 2 x 512**3 float32 that
array is 1.07 GB; undonated, a step was 3-7 % longer on the chip and the
peak 8 % higher: ``PERF.md`` section 6, PR 47). That is only sound where
nobody can look at the array afterwards, so :func:`take` hands in only a
Laplacian to which it holds the one reference (the loop's ``lap_f`` was
a local of its energy function and is gone). One that anybody still
holds by a name or in a container is a plain miss: the stage program is
the one that transforms for itself, and the array survives. What the
reference count cannot see is consumed all the same: a ``weakref`` to
the Laplacian, or a second ``jax.Array`` over its buffer
(``addressable_data``, a ``device_put`` that copies nothing), finds it
deleted after the stage.
"""

from __future__ import annotations

import contextlib
import contextvars
import itertools
import sys
import weakref

import jax

__all__ = ["LastLaplacian", "HandedIn", "take", "offer"]

#: serial -> LastLaplacian, weakly: the serial is what a program's cache
#: key holds of its collocator (:class:`HandedIn`), never the collocator
_producers = weakref.WeakValueDictionary()
_serials = itertools.count()

#: while a program's body is traced under :func:`offer`: ``(serial,
#: tracer of the array, tracer of its Laplacian)``. A context variable:
#: two threads tracing over one collocator each see their own.
_on_offer = contextvars.ContextVar("pystella_tpu_laplacian_on_offer",
                                   default=None)


def _references_to_a_local():
    value = object()
    return sys.getrefcount(value)


#: what ``sys.getrefcount`` says of an object held by one local name and
#: nothing else, on the interpreter that runs (2 on CPython up to 3.13:
#: the name and the call's own argument): read, not assumed, so that an
#: interpreter that counts otherwise still tells "nobody else" apart
_SOLE = _references_to_a_local()


class LastLaplacian:
    """The one ``(array, Laplacian)`` pair a collocator remembers.

    :arg name: what the stepper's event calls the collocator's method.
    """

    def __init__(self, name):
        self.name = str(name)
        self.serial = next(_serials)
        self._key = self._value = None   # weakref to the array; its Laplacian
        _producers[self.serial] = self

    def remember(self, key, value):
        me = weakref.ref(self)

        def key_died(ref):
            memo = me()
            if memo is not None and memo._key is ref:
                memo.forget()

        self._key, self._value = weakref.ref(key, key_died), value

    def forget(self):
        self._key = self._value = None

    def _index_among(self, leaves):
        """Where among ``leaves`` the remembered array is, or ``None``."""
        key = self._key() if self._key is not None else None
        if key is None or key.is_deleted() or self._value.is_deleted():
            return None
        return next((i for i, leaf in enumerate(leaves) if leaf is key),
                    None)

    def offered(self, tracer):
        """The Laplacian on offer for ``tracer``, or ``None``."""
        on_offer = _on_offer.get()
        if (on_offer is not None and on_offer[0] == self.serial
                and on_offer[1] is tracer):
            return on_offer[2]
        return None


@jax.tree_util.register_pytree_node_class
class HandedIn:
    """A remembered Laplacian on its way into a program: the array (the
    one leaf), and what the program is specialised on (static, so a
    program's cache keys on it): of which leaf of the program's first
    array argument it is the Laplacian, and which collocator's."""

    def __init__(self, value, leaf, producer):
        self.value, self.leaf, self.producer = value, leaf, producer

    def tree_flatten(self):
        return (self.value,), (self.leaf, self.producer)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], *aux)


def take(tree):
    """If a leaf of ``tree`` is an array some collocator remembers the
    Laplacian of, the collocator forgets the pair; and if the caller then
    holds the only reference to that Laplacian (no name, no container:
    the reference count says so), ``(HandedIn, collocator's name)``, for
    a program that consumes it. Else ``None``."""
    if not _producers:
        return None
    leaves = None
    for memo in list(_producers.values()):
        if memo._key is None:
            continue
        if leaves is None:
            leaves = jax.tree_util.tree_leaves(tree)
        index = memo._index_among(leaves)
        if index is not None:
            value = memo._value
            memo.forget()
            if sys.getrefcount(value) != _SOLE:
                return None
            return HandedIn(value, index, memo.serial), memo.name
    return None


@contextlib.contextmanager
def offer(handed, tree):
    """While a program's body is traced: ``handed.value`` (a tracer) is
    its collocator's Laplacian of leaf ``handed.leaf`` of ``tree``
    (tracers). With ``handed`` ``None`` nothing is on offer."""
    if handed is None:
        yield
        return
    token = _on_offer.set(
        (handed.producer, jax.tree_util.tree_leaves(tree)[handed.leaf],
         handed.value))
    try:
        yield
    finally:
        _on_offer.reset(token)
