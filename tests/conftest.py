import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).parent))          # proptest shim
sys.path.insert(0, str(Path(__file__).parent.parent / "src"))


@pytest.fixture(scope="session")
def unit_db():
    from repro.data.synthetic import make_dataset
    return make_dataset("unit")


@pytest.fixture(scope="session")
def unit_ip_db():
    from repro.data.synthetic import make_dataset
    return make_dataset("unit_ip")


@pytest.fixture(scope="session")
def unit_index(unit_db):
    from repro.index import Index, IndexSpec
    return Index.build(unit_db, IndexSpec.for_db(unit_db, m=8,
                                                 dfloat_recall_target=None))


@pytest.fixture(scope="session")
def unit_ip_index(unit_ip_db):
    from repro.index import Index, IndexSpec
    return Index.build(unit_ip_db, IndexSpec.for_db(unit_ip_db, m=8,
                                                    dfloat_recall_target=None))


@pytest.fixture(scope="session")
def unit_index_dfloat(unit_db):
    from repro.index import Index, IndexSpec
    return Index.build(unit_db, IndexSpec.for_db(unit_db, m=8,
                                                 dfloat_recall_target=0.80,
                                                 ef_fit=32))


@pytest.fixture(scope="session")
def unit_ip_index_dfloat(unit_ip_db):
    from repro.index import Index, IndexSpec
    return Index.build(unit_ip_db, IndexSpec.for_db(unit_ip_db, m=8,
                                                    dfloat_recall_target=0.80,
                                                    ef_fit=32))


@pytest.fixture
def unit_store(request):
    """``unit_store(storage, metric)`` -> the unit corpus of that metric and
    the index its storage is served from: the Dfloat index for the packed
    and tiered stores, the float32 one for f32."""
    def get(storage, metric="l2"):
        ip = "_ip" if metric == "ip" else ""
        dfloat = "" if storage == "f32" else "_dfloat"
        return (request.getfixturevalue(f"unit{ip}_db"),
                request.getfixturevalue(f"unit{ip}_index{dfloat}"))

    return get
