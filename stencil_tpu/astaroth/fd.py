"""6th-order centered finite differences over halo-padded blocks.

TPU-native re-derivation of Astaroth's derivative stencils (reference:
astaroth/user_kernels.h:36-127 — first/second/cross derivative pencils of
STENCIL_ORDER 6). The reference gathers a 7-point pencil per thread; here
each derivative is a sum of shifted array slices over a whole region, which
XLA fuses into one bandwidth-bound pass (and prunes any derivative an
equation never consumes).

All functions take the full padded block (leading dims allowed, data dims
``[z, y, x]`` with >= 3 cells of halo) and a ``Rect3`` in allocation-local
coordinates selecting the cells to produce.

:func:`field_data` and :func:`gradient_of_divergence` are the ONE
formulation of the pencils that both the XLA path and the fused kernel
execute. A field's slices are read once each (value, y+-i, z+-i) and both
derivatives of an axis come from the same six; everything shifted in x is
shifted AFTER it is formed: the field's own x pencil is its centre rows
shifted, and the mixed x derivatives are the shifted y and z differences
the gradient already has,

    f(y+i,x+i) + f(y-i,x-i) - f(y-i,x+i) - f(y+i,x-i) = (S_{+i} - S_{-i}) Dy_i

with ``Dy_i = f(y+i) - f(y-i)`` and ``S_d`` a shift by ``d`` in x, so that
``derxy(f) = Σ_i (S_{+i} - S_{-i}) (c_i k_xy Dy_i)``; a shift is linear, so
the two mixed terms of a column of :func:`gradient_of_divergence` share one
set of shifts. HOW a formed value is shifted is the array's business
(:func:`_x_shift`): a slice of a value formed over x-extended rows, or the
roll a view of whole rows supplies as ``xroll``.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable, Tuple

from ..geometry import Dim3, Rect3

# centered-difference coefficients (reference: user_kernels.h:38-66); the
# grid's 1/ds factors are multiplied into them where a pencil is formed, so
# that a tap costs one product
FIRST_COEFFS = (3.0 / 4.0, -3.0 / 20.0, 1.0 / 60.0)
SECOND_CENTER = -49.0 / 18.0
SECOND_COEFFS = (3.0 / 2.0, -3.0 / 20.0, 1.0 / 90.0)
CROSS_COEFFS = (270.0 / 720.0, -27.0 / 720.0, 2.0 / 720.0)
_TAPS = range(1, len(FIRST_COEFFS) + 1)  # a pencil's offsets +-i


def _sh(arr, rect: Rect3, dz: int, dy: int, dx: int):
    return arr[
        ...,
        slice(rect.lo.z + dz, rect.hi.z + dz),
        slice(rect.lo.y + dy, rect.hi.y + dy),
        slice(rect.lo.x + dx, rect.hi.x + dx),
    ]


def _sum(terms):
    """Left to right, from the first term."""
    terms = iter(terms)
    res = next(terms)
    for t in terms:
        res = res + t
    return res


def _first_of(diffs, inv_ds):
    """First derivative from the differences ``f(+i) - f(-i)``, i = 1..3."""
    return _sum((c * inv_ds) * d for c, d in zip(FIRST_COEFFS, diffs))


def _second_of(centre, pairs, inv_ds):
    """Second derivative from the centre and the pairs ``(f(+i), f(-i))``."""
    k = inv_ds * inv_ds
    return _sum([(SECOND_CENTER * k) * centre]
                + [(c * k) * (p + q) for c, (p, q) in zip(SECOND_COEFFS, pairs)])


def _first(arr, rect, axis_shift, inv_ds):
    """axis_shift(i) -> (dz, dy, dx) for offset i along the axis."""
    return _first_of(
        [_sh(arr, rect, *axis_shift(i)) - _sh(arr, rect, *axis_shift(-i))
         for i in _TAPS], inv_ds)


def _second(arr, rect, axis_shift, inv_ds):
    return _second_of(
        _sh(arr, rect, 0, 0, 0),
        [(_sh(arr, rect, *axis_shift(i)), _sh(arr, rect, *axis_shift(-i)))
         for i in _TAPS], inv_ds)


def _cross(arr, rect, shift_a, shift_b, inv_ds_a, inv_ds_b):
    """Cross derivative from the two diagonal pencils
    (reference: user_kernels.h:62-75)."""
    k = inv_ds_a * inv_ds_b
    return _sum(
        (c * k) * (
            _sh(arr, rect, *shift_a(i))
            + _sh(arr, rect, *shift_a(-i))
            - _sh(arr, rect, *shift_b(i))
            - _sh(arr, rect, *shift_b(-i))
        )
        for i, c in enumerate(CROSS_COEFFS, start=1)
    )


def derx(arr, rect, inv_dsx):
    return _first(arr, rect, lambda i: (0, 0, i), inv_dsx)


def dery(arr, rect, inv_dsy):
    return _first(arr, rect, lambda i: (0, i, 0), inv_dsy)


def derz(arr, rect, inv_dsz):
    return _first(arr, rect, lambda i: (i, 0, 0), inv_dsz)


def derxx(arr, rect, inv_dsx):
    return _second(arr, rect, lambda i: (0, 0, i), inv_dsx)


def deryy(arr, rect, inv_dsy):
    return _second(arr, rect, lambda i: (0, i, 0), inv_dsy)


def derzz(arr, rect, inv_dsz):
    return _second(arr, rect, lambda i: (i, 0, 0), inv_dsz)


def derxy(arr, rect, inv_dsx, inv_dsy):
    return _cross(
        arr, rect, lambda i: (0, i, i), lambda i: (0, -i, i), inv_dsx, inv_dsy
    )


def derxz(arr, rect, inv_dsx, inv_dsz):
    return _cross(
        arr, rect, lambda i: (i, 0, i), lambda i: (-i, 0, i), inv_dsx, inv_dsz
    )


def deryz(arr, rect, inv_dsy, inv_dsz):
    return _cross(
        arr, rect, lambda i: (i, i, 0), lambda i: (-i, i, 0), inv_dsy, inv_dsz
    )


@dataclass
class FieldData:
    """value + gradient + symmetric hessian of one scalar field over a
    region (reference: user_kernels.h AcRealData / read_data).

    The diagonal of the hessian is formed with the gradient; the mixed
    entries are formed when first read (an equation that reads none costs
    none), the x ones from ``dy`` / ``dz``: the differences
    ``f(+i) - f(-i)``, i = 1..3, over the rows ``xshift(value, d)``
    shifts by ``d`` in x onto the region."""

    value: Any
    gx: Any
    gy: Any
    gz: Any
    hxx: Any
    hyy: Any
    hzz: Any
    dy: Tuple[Any, Any, Any]
    dz: Tuple[Any, Any, Any]
    xshift: Callable[[Any, int], Any]
    inv_ds: Tuple[float, float, float]
    hyz_of: Callable[[], Any]
    memo: dict = field(default_factory=dict, repr=False)

    def _once(self, key, make):
        if key not in self.memo:
            self.memo[key] = make()
        return self.memo[key]

    @property
    def hxy(self):
        k = self.inv_ds[0] * self.inv_ds[1]
        return self._once("hxy", lambda: _x_differences(
            self.xshift, _weighted(CROSS_COEFFS, k, self.dy)))

    @property
    def hxz(self):
        k = self.inv_ds[0] * self.inv_ds[2]
        return self._once("hxz", lambda: _x_differences(
            self.xshift, _weighted(CROSS_COEFFS, k, self.dz)))

    @property
    def hyz(self):
        return self._once("hyz", self.hyz_of)

    @property
    def gradient(self):
        return (self.gx, self.gy, self.gz)

    def laplace(self):
        """trace of the hessian (reference: user_kernels.h:226-229)."""
        return self.hxx + self.hyy + self.hzz


def _weighted(coeffs, k, diffs):
    return [(c * k) * d for c, d in zip(coeffs, diffs)]


def _x_differences(xshift, terms):
    """Σ_i (S_{+i} - S_{-i}) terms[i]. The six shifts come first, in one
    run: a kernel's rolls keep their program order, and a run of them
    flies under the arithmetic issued before it."""
    shifted = [(xshift(t, i), xshift(t, -i)) for i, t in enumerate(terms, start=1)]
    return _sum(plus - minus for plus, minus in shifted)


def _x_shift(arr):
    """``(margin, xshift)``: how a value formed from ``arr``'s rows is
    shifted in x. A view of whole periodic rows says so with ``xroll``
    (the fused kernel's tight-x window: a lane roll, no margin); any other
    array has its x halos inline, the value is formed over rows extended
    by the margin and the shift is a slice of it."""
    roll = getattr(arr, "xroll", None)
    if roll is not None:
        return 0, roll
    m = len(FIRST_COEFFS)
    return m, lambda v, d: v[..., m + d : v.shape[-1] - m + d]


def field_data(arr, rect: Rect3, inv_ds) -> FieldData:
    """Build value/gradient/hessian for one field over ``rect``.

    ``inv_ds`` is (inv_dsx, inv_dsy, inv_dsz)."""
    ix, iy, iz = inv_ds
    m, xshift = _x_shift(arr)
    rows = Rect3(Dim3(rect.lo.x - m, rect.lo.y, rect.lo.z),
                 Dim3(rect.hi.x + m, rect.hi.y, rect.hi.z))
    centre = _sh(arr, rows, 0, 0, 0)
    x = [(xshift(centre, i), xshift(centre, -i)) for i in _TAPS]
    value = xshift(centre, 0)
    y = [(_sh(arr, rows, 0, i, 0), _sh(arr, rows, 0, -i, 0)) for i in _TAPS]
    z = [(_sh(arr, rows, i, 0, 0), _sh(arr, rows, -i, 0, 0)) for i in _TAPS]
    dy = tuple(p - q for p, q in y)
    dz = tuple(p - q for p, q in z)

    def here(pairs):
        return [(xshift(p, 0), xshift(q, 0)) for p, q in pairs]

    return FieldData(
        value=value,
        gx=_first_of([p - q for p, q in x], ix),
        gy=_first_of([xshift(d, 0) for d in dy], iy),
        gz=_first_of([xshift(d, 0) for d in dz], iz),
        hxx=_second_of(value, x, ix),
        hyy=_second_of(value, here(y), iy),
        hzz=_second_of(value, here(z), iz),
        dy=dy,
        dz=dz,
        xshift=xshift,
        inv_ds=(ix, iy, iz),
        hyz_of=lambda: deryz(arr, rect, iy, iz),
    )


def gradient_of_divergence(v):
    """Column sums of the component hessians (user_kernels.h:246-251):
    ``(v0.hxx + v1.hxy + v2.hxz, v0.hxy + v1.hyy + v2.hyz,
    v0.hxz + v1.hyz + v2.hzz)``. The two mixed terms of the x column are
    summed BEFORE they are shifted in x (one set of shifts, not two), and
    the mixed derivatives no column reads (``v2.hxy``, ``v1.hxz``) are
    never formed. Formed once a vector: momentum and entropy both ask."""
    v0, v1, v2 = v
    ix, iy, iz = v0.inv_ds

    def make():
        terms = [
            a + b for a, b in zip(_weighted(CROSS_COEFFS, ix * iy, v1.dy),
                                  _weighted(CROSS_COEFFS, ix * iz, v2.dz))
        ]
        return (
            v0.hxx + _x_differences(v0.xshift, terms),
            v0.hxy + v1.hyy + v2.hyz,
            v0.hxz + v1.hyz + v2.hzz,
        )

    return v0._once(("god", id(v1), id(v2)), make)
