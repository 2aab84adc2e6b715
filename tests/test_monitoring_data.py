"""Monitoring bus + data-pipeline coverage."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.deviceflow import DeviceFlow, Message, Delivery
from repro.core.federation import AggregationService, ClientCountTrigger
from repro.core.monitoring import (
    InMemorySink, MetricEvent, MetricsBus, TaskMonitor,
    wire_aggregation_service,
)
from repro.core.strategies import AccumulatedStrategy
from repro.data.partition import (
    dirichlet_partition, iid_partition, label_skew_partition,
)
from repro.data.tokens import TokenPipeline


def test_monitor_aggregation_feed():
    bus = MetricsBus()
    svc = AggregationService({"w": jnp.zeros(2)},
                             trigger=ClientCountTrigger(2))
    wire_aggregation_service(bus, svc, task_id=7)
    mon = TaskMonitor(bus, task_id=7)
    flow = DeviceFlow(svc)
    flow.register_task(7, AccumulatedStrategy(thresholds=(1,)))
    for i in range(4):
        flow.submit(Message(7, i, 0, {"w": jnp.ones(2)}, num_samples=5))
    s = mon.summary()
    assert s["aggregations"] == 2
    assert s["clients_aggregated"] == 4
    assert "aggregations" in mon.to_json()


def test_monitor_filters_other_tasks():
    bus = MetricsBus()
    mon = TaskMonitor(bus, task_id=1)
    bus.emit(MetricEvent(0.0, "cloud", 2, "aggregation", {"num_clients": 3}))
    bus.emit(MetricEvent(0.0, "cloud", 1, "aggregation", {"num_clients": 5}))
    assert mon.summary()["clients_aggregated"] == 5


def test_token_pipeline_determinism_and_restart():
    p1 = TokenPipeline(vocab_size=512, seq_len=16, batch_size=4, seed=3)
    b1 = [next(p1) for _ in range(3)]
    state = p1.state_dict()
    b_next = next(p1)
    # Restore into a fresh pipeline -> identical continuation.
    p2 = TokenPipeline(vocab_size=512, seq_len=16, batch_size=4, seed=3)
    p2.load_state_dict(state)
    b_next2 = next(p2)
    np.testing.assert_array_equal(b_next.tokens, b_next2.tokens)
    # Different hosts draw different streams.
    ph = TokenPipeline(vocab_size=512, seq_len=16, batch_size=4, seed=3,
                       host_id=1, num_hosts=2)
    assert not np.array_equal(next(ph).tokens, b1[0].tokens)
    assert b1[0].tokens.max() < 512 and b1[0].tokens.min() >= 0


def test_partitioners_cover_all_records():
    labels = np.random.default_rng(0).integers(0, 2, 1000).astype(np.float32)
    for parts in (
        iid_partition(1000, 10),
        label_skew_partition(labels, 10),
        dirichlet_partition(labels, 10, alpha=0.5),
    ):
        assert len(parts) == 10
        allidx = np.concatenate(parts)
        assert len(np.unique(allidx)) == len(allidx)  # no duplicates
        assert len(allidx) >= 900  # near-total coverage


def test_label_skew_creates_noniid():
    labels = np.random.default_rng(0).integers(0, 2, 2000).astype(np.float32)
    parts = label_skew_partition(labels, 10, frac_positive_heavy=0.7,
                                 heavy_pos_share=0.8)
    rates = [labels[p].mean() for p in parts if len(p)]
    assert max(rates) - min(rates) > 0.3  # heavy vs light devices differ


def _ctr_reference(num_devices, records_per_device, dim, seed, alpha):
    """The per-record loop generator and per-device shard loop the
    vectorized ``make_federated_ctr``/``stacked_shards`` replace."""
    from repro.data import synthetic_ctr as sc

    rng = np.random.default_rng(seed)
    n = num_devices * records_per_device
    prefs = rng.integers(0, 1000, size=(8, sc._N_RAW_FIELDS))
    probs = (rng.dirichlet([alpha] * 8, size=num_devices)
             if alpha is not None else np.full((num_devices, 8), 1.0 / 8))
    device_ids = np.repeat(np.arange(num_devices, dtype=np.int32),
                           records_per_device)
    seg = np.array([rng.choice(8, p=probs[d]) for d in device_ids],
                   dtype=np.int32)
    raw = prefs[seg] + rng.integers(0, 50, size=(n, sc._N_RAW_FIELDS))
    return seg, raw


@pytest.mark.parametrize("alpha", [None, 0.3])
def test_synthetic_ctr_vectorized_matches_loop_reference(alpha):
    """Seeded data is unchanged by the vectorized draw, and the vectorized
    stacking keeps each device's first R records in dataset order."""
    from repro.data import synthetic_ctr as sc

    seg, raw = _ctr_reference(40, 6, 32, 5, alpha)
    data = sc.make_federated_ctr(num_devices=40, records_per_device=6,
                                 dim=32, seed=5, noniid_alpha=alpha)
    feats = np.zeros((len(raw), 32), np.float32)
    for f in range(sc._N_RAW_FIELDS):
        feats[np.arange(len(raw)), (raw[:, f] * 2654435761 + f * 97) % 32] += 1
    feats /= np.sqrt(sc._N_RAW_FIELDS)
    np.testing.assert_array_equal(data.features, feats)
    # Shuffle records so devices interleave; ask for absent and short ids.
    perm = np.random.default_rng(1).permutation(len(data.labels))
    shuffled = sc.CTRDataset(data.features[perm], data.labels[perm],
                             data.device_ids[perm], 40, 32)
    ids = np.array([3, 39, 77, 0, 3])
    X, Y, counts = shuffled.stacked_shards(ids, 4)
    for i, d in enumerate(ids):
        x, y = shuffled.device_shard(int(d))
        k = min(len(x), 4)
        assert counts[i] == k
        np.testing.assert_array_equal(X[i, :k], x[:k])
        np.testing.assert_array_equal(Y[i, :k], y[:k])
        assert not X[i, k:].any() and not Y[i, k:].any()
