"""repro.kernels.numerics: the backend-independent helpers reproduce the
plain jnp ops bit-for-bit on XLA:CPU, so CPU wire bytes are unchanged."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import numerics

SUM_SIZES = [1, 2, 8, 31, 32, 33, 100, 1000, 1024, 4095, 4096, 5000,
             65536, 100003, 1 << 20]


def _vec(d, seed):
    rng = np.random.default_rng([seed, d])
    return (rng.standard_normal(d) * rng.uniform(0.25, 2.0)
            + rng.uniform(-0.5, 0.5)).astype(np.float32)


@pytest.mark.parametrize("d", SUM_SIZES)
def test_ordered_sum_is_xla_cpu_sum(d):
    for seed in range(3):
        x = _vec(d, seed)
        got = np.asarray(jax.jit(numerics.ordered_sum)(x))
        want = np.asarray(jax.jit(jnp.sum)(x))
        assert got.view(np.uint32) == want.view(np.uint32), (d, seed)
        got = np.asarray(jax.jit(numerics.ordered_mean)(x))
        want = np.asarray(jax.jit(jnp.mean)(x))
        assert got.view(np.uint32) == want.view(np.uint32), (d, seed)


@pytest.mark.parametrize("d", [100, 4096, 1 << 18])
def test_ordered_sum_of_fused_select_is_xla_cpu_sum(d):
    # the EF twin's cluster sums: a where() fused into the reduction
    x = _vec(d, 7)

    def pair(v):
        hi = v >= jnp.float32(0.1)
        masked = jnp.where(hi, v, 0.0)
        return numerics.ordered_sum(masked), jnp.sum(masked)

    got, want = (np.asarray(a) for a in jax.jit(pair)(x))
    assert got.view(np.uint32) == want.view(np.uint32)


def test_ordered_sum_is_a_chain_of_elementwise_adds():
    # no reduce op left for a backend to reorder
    txt = jax.jit(numerics.ordered_sum).lower(
        jnp.zeros((1 << 16,), jnp.float32)).as_text()
    assert "reduce" not in txt


@pytest.mark.parametrize("d", [10, 4097, 1 << 17])
def test_count_prefix_is_cumsum(d):
    rng = np.random.default_rng(d)
    c = rng.random(d) < 0.3
    got = np.asarray(numerics.count_prefix(jnp.asarray(c)))
    np.testing.assert_array_equal(got, np.cumsum(c.astype(np.int32)))


def test_div_rn_is_ieee_quotient():
    rng = np.random.default_rng(3)
    a = (rng.standard_normal(1 << 16) * 10).astype(np.float32)
    b = rng.uniform(0.01, 100.0, 1 << 16).astype(np.float32)
    got = np.asarray(jax.jit(numerics.div_rn)(a, b))
    np.testing.assert_array_equal(got.view(np.uint32),
                                  (a / b).view(np.uint32))


def _round_f32(v):
    """Exact rational ``v`` rounded to the nearest-even normal f32."""
    from fractions import Fraction
    if v == 0:
        return np.float32(0.0)
    sign, m = (-1, -v) if v < 0 else (1, v)
    e = m.numerator.bit_length() - m.denominator.bit_length() - 24
    while m / Fraction(2) ** e >= 1 << 24:
        e += 1
    while m / Fraction(2) ** e < 1 << 23:
        e -= 1
    q = m / Fraction(2) ** e
    n = q.numerator // q.denominator
    rem = q - n
    if rem > Fraction(1, 2) or (rem == Fraction(1, 2) and n % 2 == 1):
        n += 1
    return np.float32(sign * n * 2.0 ** e)


def _fma_cases():
    rng = np.random.default_rng(11)
    k = 2000
    a = (rng.standard_normal(k) * 2.0 ** rng.integers(-20, 20, k)
         ).astype(np.float32)
    b = (rng.standard_normal(k) * 2.0 ** rng.integers(-20, 20, k)
         ).astype(np.float32)
    c = (rng.standard_normal(k) * 2.0 ** rng.integers(-40, 40, k)
         ).astype(np.float32)
    # cancellation: c = −RN(a·b) (+ an ulp), so only the product's low
    # half survives
    j = rng.permutation(k)[:k // 2]
    c[j] = -(a[j] * b[j])
    c[j[::2]] = np.nextafter(c[j[::2]], np.float32(np.inf))
    # ties: a·b = 1 + 2^-12 + 2^-12 + 2^-24 lands halfway once c shifts it
    a[:8] = b[:8] = np.float32(1 + 2.0 ** -12)
    c[:8] = np.float32(2.0 ** -24) * np.array([1, -1, 3, -3, 0.5, -0.5, 2, -2],
                                               np.float32)
    # the ternary pass-through numerator: x − p·c with p ∈ [0, 1]
    p = rng.uniform(0, 1, 64).astype(np.float32)
    a[8:72], b[8:72], c[8:72] = -p, rng.uniform(-4, 4, 64).astype(
        np.float32), rng.uniform(-4, 4, 64).astype(np.float32)
    return a, b, c


def test_fma_rn_is_one_rounding():
    from fractions import Fraction
    a, b, c = _fma_cases()
    got = np.asarray(jax.jit(numerics.fma_rn)(a, b, c))
    want = np.array([_round_f32(Fraction(float(x)) * Fraction(float(y))
                                + Fraction(float(z)))
                     for x, y, z in zip(a, b, c)], np.float32)
    np.testing.assert_array_equal(got.view(np.uint32), want.view(np.uint32))


def test_fma_rn_zero_operands_are_exact():
    a = np.array([0.0, 1.5, -2.0, 0.0, 3.0], np.float32)
    b = np.array([7.0, 0.0, 0.25, 0.0, -1.0], np.float32)
    c = np.array([1.25, -3.0, 0.0, 0.0, 3.0], np.float32)
    got = np.asarray(jax.jit(numerics.fma_rn)(a, b, c))
    np.testing.assert_array_equal(got, a * b + c)
