import jax


def test_backend_is_virtual_cpu(devices8):
    assert jax.default_backend() == "cpu"
    assert len(devices8) == 8

