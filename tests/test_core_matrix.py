"""Configuration matrix + hashing: unit and property tests."""
import itertools

import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import ConfigMatrix, ConfigMatrixError, HashingError
from repro.core.hashing import canonicalize, stable_hash, task_key


def model_a():
    return "a"


def model_b():
    return "b"


class TestExpansion:
    def test_paper_example_counts(self):
        # 3 x 2 x 3 x 3 = 54 tasks, exactly the paper's example.
        m = ConfigMatrix.from_dict(
            {
                "parameters": {
                    "dataset": ["digits", "wine", "cancer"],
                    "feature_engineering": ["dummy", "simple"],
                    "preprocessing": ["none", "minmax", "standard"],
                    "model": [model_a, model_b, "svc"],
                },
                "settings": {"n_fold": 5},
            }
        )
        assert m.cartesian_size == 54
        tasks = m.task_list()
        assert len(tasks) == 54
        assert all(t.settings == {"n_fold": 5} for t in tasks)

    def test_exclude_is_partial_match_lookup(self):
        m = ConfigMatrix.from_dict(
            {
                "parameters": {"a": [1, 2, 3], "b": ["x", "y"]},
                "exclude": [{"a": 2}],  # kills every combo with a=2
            }
        )
        combos = list(m.combinations())
        assert len(combos) == 4
        assert all(c["a"] != 2 for c in combos)

    def test_exclude_full_assignment(self):
        m = ConfigMatrix.from_dict(
            {
                "parameters": {"a": [1, 2], "b": ["x", "y"]},
                "exclude": [{"a": 1, "b": "y"}],
            }
        )
        combos = list(m.combinations())
        assert {"a": 1, "b": "y"} not in combos
        assert len(combos) == 3

    def test_exclude_matches_callables(self):
        m = ConfigMatrix.from_dict(
            {
                "parameters": {"model": [model_a, model_b]},
                "exclude": [{"model": model_a}],
            }
        )
        assert [c["model"] for c in m.combinations()] == [model_b]

    def test_task_indices_stable_and_keys_unique(self):
        m = ConfigMatrix.from_dict({"parameters": {"a": [1, 2], "b": [3, 4]}})
        tasks = m.task_list()
        assert [t.index for t in tasks] == [0, 1, 2, 3]
        assert len({t.key for t in tasks}) == 4

    def test_shard_partition(self):
        m = ConfigMatrix.from_dict({"parameters": {"a": list(range(10))}})
        parts = [m.shard(i, 3) for i in range(3)]
        all_idx = sorted(t.index for p in parts for t in p)
        assert all_idx == list(range(10))

    @pytest.mark.parametrize(
        "bad",
        [
            {},
            {"parameters": {}},
            {"parameters": {"a": []}},
            {"parameters": {"a": [1]}, "bogus": 1},
            {"parameters": {"a": [1]}, "exclude": [{"zzz": 1}]},
            {"parameters": {"a": "not-a-list"}},
        ],
    )
    def test_invalid_matrices_rejected(self, bad):
        with pytest.raises(ConfigMatrixError):
            ConfigMatrix.from_dict(bad)

    @given(
        sizes=st.lists(st.integers(min_value=1, max_value=4), min_size=1, max_size=4),
        n_excl=st.integers(min_value=0, max_value=5),
        seed=st.integers(min_value=0, max_value=10_000),
    )
    @settings(max_examples=40, deadline=None)
    def test_property_count_equals_product_minus_excluded(self, sizes, n_excl, seed):
        import random

        rng = random.Random(seed)
        params = {f"p{i}": list(range(n)) for i, n in enumerate(sizes)}
        full = list(itertools.product(*params.values()))
        names = list(params.keys())
        excl = []
        for _ in range(n_excl):
            combo = rng.choice(full)
            keys = rng.sample(names, rng.randint(1, len(names)))
            excl.append({k: combo[names.index(k)] for k in keys})
        m = ConfigMatrix.from_dict({"parameters": params, "exclude": excl})
        expected = [
            c
            for c in full
            if not any(
                all(c[names.index(k)] == v for k, v in rule.items()) for rule in excl
            )
        ]
        assert len(list(m.combinations())) == len(expected)


class TestAlgebra:
    """The v2 compositional matrix API: + * where derive."""

    def _keys(self, m):
        return [t.key for t in m.tasks()]

    def test_chain_concatenates_and_dedups(self):
        m1 = ConfigMatrix.from_dict({"parameters": {"a": [1, 2]}})
        m2 = ConfigMatrix.from_dict({"parameters": {"a": [2, 3]}})
        chained = m1 + m2
        params = [t.params for t in chained.tasks()]
        assert params == [{"a": 1}, {"a": 2}, {"a": 3}]  # a=2 de-duped by key
        assert len(m1 + m1) == len(m1)

    def test_chain_accepts_paper_dicts_and_flattens(self):
        m1 = ConfigMatrix.from_dict({"parameters": {"a": [1]}})
        c = m1 + {"parameters": {"a": [2]}} + {"parameters": {"a": [3]}}
        assert [t.params["a"] for t in c.tasks()] == [1, 2, 3]
        assert len(c.parts) == 3  # flattened, not nested chains

    def test_chain_keeps_distinct_settings_distinct(self):
        # Identical params under different settings are different tasks.
        m1 = ConfigMatrix.from_dict({"parameters": {"a": [1]}, "settings": {"s": 1}})
        m2 = ConfigMatrix.from_dict({"parameters": {"a": [1]}, "settings": {"s": 2}})
        assert len(m1 + m2) == 2

    def test_product_matches_single_matrix(self):
        m1 = ConfigMatrix.from_dict({"parameters": {"a": [1, 2], "b": ["x", "y"]}})
        m2 = ConfigMatrix.from_dict({"parameters": {"c": [True, False]}})
        combined = ConfigMatrix.from_dict(
            {"parameters": {"a": [1, 2], "b": ["x", "y"], "c": [True, False]}}
        )
        assert set(self._keys(m1 * m2)) == set(self._keys(combined))

    def test_product_rejects_overlapping_axes(self):
        m1 = ConfigMatrix.from_dict({"parameters": {"a": [1]}})
        with pytest.raises(ConfigMatrixError):
            m1 * {"parameters": {"a": [2]}}

    def test_product_merges_settings_and_rejects_conflicts(self):
        m1 = ConfigMatrix.from_dict({"parameters": {"a": [1]}, "settings": {"s": 1}})
        m2 = ConfigMatrix.from_dict({"parameters": {"b": [2]}, "settings": {"t": 2}})
        (task,) = (m1 * m2).tasks()
        assert task.settings == {"s": 1, "t": 2}
        bad = ConfigMatrix.from_dict({"parameters": {"c": [3]}, "settings": {"s": 9}})
        with pytest.raises(ConfigMatrixError):
            list((m1 * bad).tasks())

    def test_where_equivalent_to_dict_exclude(self):
        base = {"parameters": {"a": [1, 2, 3], "b": ["x", "y"]}}
        excluded = ConfigMatrix.from_dict({**base, "exclude": [{"a": 2}]})
        filtered = ConfigMatrix.from_dict(base).where(lambda p: p["a"] != 2)
        assert self._keys(filtered) == self._keys(excluded)

    def test_derive_adds_param_and_changes_key(self):
        m = ConfigMatrix.from_dict({"parameters": {"a": [1, 2]}})
        d = m.derive("a_sq", lambda p: p["a"] ** 2)
        tasks = list(d.tasks())
        assert [t.params for t in tasks] == [{"a": 1, "a_sq": 1}, {"a": 2, "a_sq": 4}]
        assert set(self._keys(d)).isdisjoint(self._keys(m))
        assert d.axis_names == ["a", "a_sq"]
        # Deriving with a different function produces different identities.
        d2 = m.derive("a_sq", lambda p: p["a"] ** 3)
        assert self._keys(d)[1] != self._keys(d2)[1]

    def test_derive_rejects_axis_collision(self):
        m = ConfigMatrix.from_dict({"parameters": {"a": [1]}})
        with pytest.raises(ConfigMatrixError):
            m.derive("a", lambda p: 0)

    def test_operators_compose(self):
        m = (
            ConfigMatrix.from_dict({"parameters": {"a": [1, 2, 3]}})
            * {"parameters": {"b": [10, 20]}}
        ).where(lambda p: p["a"] != 2).derive("ab", lambda p: p["a"] * p["b"])
        tasks = m.task_list()
        assert len(tasks) == 4
        assert all(t.params["ab"] == t.params["a"] * t.params["b"] for t in tasks)
        assert [t.index for t in tasks] == [0, 1, 2, 3]

    def test_key_stability_across_constructions(self):
        build = lambda: (
            ConfigMatrix.from_dict(
                {"parameters": {"a": [1, 2]}, "settings": {"s": 5}}
            )
            * {"parameters": {"b": ["x"]}}
        ).derive("twice", _twice)
        assert self._keys(build()) == self._keys(build())

    @given(
        width_a=st.integers(min_value=1, max_value=4),
        width_b=st.integers(min_value=1, max_value=4),
        cut=st.integers(min_value=0, max_value=3),
    )
    @settings(max_examples=25, deadline=None)
    def test_property_product_then_where_counts(self, width_a, width_b, cut):
        m1 = ConfigMatrix.from_dict({"parameters": {"a": list(range(width_a))}})
        m2 = ConfigMatrix.from_dict({"parameters": {"b": list(range(width_b))}})
        prod = m1 * m2
        assert len(prod) == width_a * width_b
        kept = prod.where(lambda p: p["a"] != cut)
        expected = (width_a - (1 if cut < width_a else 0)) * width_b
        if expected == 0:
            with pytest.raises(ConfigMatrixError):
                kept.task_list()
        else:
            assert len(kept.task_list()) == expected
            # where() must agree with the paper's dict exclude.
            dict_form = ConfigMatrix.from_dict(
                {
                    "parameters": {"a": list(range(width_a)), "b": list(range(width_b))},
                    "exclude": [{"a": cut}] if cut < width_a else [],
                }
            )
            assert {t.key for t in kept.tasks()} == {t.key for t in dict_form.tasks()}

    @given(
        values=st.lists(st.integers(min_value=0, max_value=6), min_size=1, max_size=8),
    )
    @settings(max_examples=25, deadline=None)
    def test_property_chain_self_union_idempotent(self, values):
        m = ConfigMatrix.from_dict({"parameters": {"a": values}})
        expect = len(set(values))
        assert len(m + m) == expect
        assert len((m + m) + m) == expect


def _twice(p):
    return p["a"] * 2


class TestSettingsInKey:
    """Satellite: settings (and namespace) are part of task identity."""

    def test_same_params_different_settings_different_key(self):
        m1 = ConfigMatrix.from_dict({"parameters": {"a": [1]}, "settings": {"s": 1}})
        m2 = ConfigMatrix.from_dict({"parameters": {"a": [1]}, "settings": {"s": 2}})
        (t1,), (t2,) = m1.task_list(), m2.task_list()
        assert t1.params == t2.params
        assert t1.key != t2.key

    def test_namespace_changes_key(self):
        m = ConfigMatrix.from_dict({"parameters": {"a": [1]}})
        (plain,) = m.task_list()
        (ns,) = m.task_list(namespace="serve")
        assert plain.key != ns.key
        assert m.task_list(namespace="serve")[0].key == ns.key

    def test_task_key_function_folds_settings(self):
        assert task_key({"a": 1}) == task_key({"a": 1}, settings={})
        assert task_key({"a": 1}) != task_key({"a": 1}, settings={"s": 1})
        assert task_key({"a": 1}, namespace="x") != task_key({"a": 1}, namespace="y")


class TestHashing:
    def test_dict_order_invariance(self):
        assert stable_hash({"a": 1, "b": 2}) == stable_hash({"b": 2, "a": 1})

    def test_nested_structures(self):
        v1 = {"x": [1, 2, {"y": (3, 4)}], "s": {2, 1}}
        v2 = {"s": {1, 2}, "x": [1, 2, {"y": [3, 4]}]}  # tuple/list normalise
        assert stable_hash(v1) == stable_hash(v2)

    def test_callables_by_qualified_name(self):
        assert stable_hash(model_a) != stable_hash(model_b)
        assert stable_hash(model_a) == stable_hash(model_a)

    def test_lambda_rejected(self):
        with pytest.raises(HashingError):
            stable_hash(lambda x: x)

    def test_closure_rejected(self):
        def outer():
            def inner():
                return 1

            return inner

        with pytest.raises(HashingError):
            stable_hash(outer())

    def test_dataclass_and_model_config(self):
        from repro.configs.registry import get_config

        c1 = get_config("qwen3-8b")
        c2 = get_config("qwen3-8b")
        assert stable_hash(c1) == stable_hash(c2)
        assert stable_hash(c1) != stable_hash(get_config("llama3.2-3b"))

    def test_numpy_values(self):
        import numpy as np

        a = np.arange(6).reshape(2, 3)
        assert stable_hash(a) == stable_hash(a.copy())
        assert stable_hash(a) != stable_hash(a.T)
        assert stable_hash(np.float32(1.5)) == stable_hash(1.5)

    def test_float_specials(self):
        assert stable_hash(float("nan")) == stable_hash(float("nan"))
        assert stable_hash(float("inf")) != stable_hash(float("-inf"))

    @given(st.dictionaries(st.text(min_size=1, max_size=8), st.integers(), min_size=1, max_size=6))
    @settings(max_examples=30, deadline=None)
    def test_property_shuffled_dict_same_key(self, d):
        items = list(d.items())
        assert task_key(dict(items)) == task_key(dict(reversed(items)))


class TestTaskViews:
    """shard()/subset() return lazy MatrixBase views that keep composing."""

    def _m(self, n=10):
        return ConfigMatrix.from_dict({"parameters": {"a": list(range(n))}})

    def test_views_are_matrices_and_iterate_like_lists(self):
        from repro.core import MatrixBase, TaskViewMatrix

        view = self._m().shard(0, 3)
        assert isinstance(view, MatrixBase) and isinstance(view, TaskViewMatrix)
        # list behavior via iteration / .tasks(): base indices are preserved
        assert [t.index for t in view] == [0, 3, 6, 9]
        assert [t.index for t in view.tasks()] == [0, 3, 6, 9]
        assert len(view) == 4

    def test_shard_keys_match_full_matrix(self):
        m = self._m()
        full = {t.index: t.key for t in m.task_list()}
        for i in range(3):
            for t in m.shard(i, 3):
                assert t.key == full[t.index], "sharding must not rekey tasks"

    def test_subset_chains_with_algebra(self):
        m = self._m(6)
        other = ConfigMatrix.from_dict({"parameters": {"b": [0, 1]}})
        comp = (m.subset(lambda p: p["a"] % 2 == 0) * other).where(
            lambda p: p["a"] + p["b"] < 5
        )
        combos = sorted((t.params["a"], t.params["b"]) for t in comp.tasks())
        assert combos == [(0, 0), (0, 1), (2, 0), (2, 1), (4, 0)]

    def test_shard_union_roundtrips(self):
        m = self._m(7)
        union = m.shard(0, 2) + m.shard(1, 2)
        assert sorted(t.params["a"] for t in union.tasks()) == list(range(7))
        # de-dup by key: overlapping shards collapse
        overlap = m.shard(0, 2) + m.shard(0, 2)
        assert len(overlap.task_list()) == 4
