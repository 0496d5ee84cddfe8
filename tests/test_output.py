"""Output-layer tests: the pod-scale sharded snapshot path (reference
analog: the x-slice-streamed gather_array + rank-0 write,
decomp.py:536-599), and ``OutputFile.output``'s appends against the plain
statements it is equal to (``_plain_output``, upstream's
output.py:157-181); tests/test_examples.py reads a whole run's file
back."""

import numpy as np
import pytest

import pystella_tpu as ps


@pytest.mark.parametrize("proc_shape", [(1, 1, 1), (2, 2, 2)],
                         indirect=True)
@pytest.mark.parametrize("grid_shape", [(16, 16, 16)], indirect=True)
def test_sharded_snapshot_roundtrip(make_decomp, grid_shape, proc_shape,
                                    tmp_path):
    """save() writes only addressable shards with global offsets; load()
    reassembles the exact global array — for unsharded, 3-axis-sharded,
    and outer-axis arrays."""
    decomp = make_decomp(proc_shape)
    rng = np.random.default_rng(3)
    f = rng.standard_normal((2,) + grid_shape)
    rho = rng.standard_normal(grid_shape).astype(np.float32)

    d = str(tmp_path / "snaps")
    with ps.ShardedSnapshot(d) as snap:
        snap.save(0, f=decomp.shard(f), rho=decomp.shard(rho))
        snap.save(40, f=decomp.shard(2 * f))

    assert ps.ShardedSnapshot.steps(d) == [0, 40]
    back = ps.ShardedSnapshot.load(d, 0)
    assert back["f"].dtype == f.dtype and back["rho"].dtype == np.float32
    assert np.array_equal(back["f"], f)
    assert np.array_equal(back["rho"], rho)
    assert np.array_equal(ps.ShardedSnapshot.load(d, 40)["f"], 2 * f)

    with pytest.raises(KeyError):
        ps.ShardedSnapshot.load(d, 7)


def test_sharded_snapshot_plain_numpy(tmp_path):
    """Host arrays (no shards) write as a single block."""
    d = str(tmp_path / "snaps")
    x = np.arange(24.0).reshape(2, 3, 4)
    with ps.ShardedSnapshot(d) as snap:
        snap.save(1, x=x)
    assert np.array_equal(ps.ShardedSnapshot.load(d, 1)["x"], x)


@pytest.mark.parametrize("proc_shape", [(2, 2, 2)], indirect=True)
@pytest.mark.parametrize("grid_shape", [(16, 16, 16)], indirect=True)
def test_sharded_snapshot_merge_streams(make_decomp, grid_shape,
                                        proc_shape, tmp_path):
    """merge() streams shard blocks straight into one output HDF5
    (peak memory = one shard — the reference's x-slice-streamed gather
    analog) and its box-tiling coverage check catches missing shards
    without a full boolean mask."""
    import h5py
    decomp = make_decomp(proc_shape)
    rng = np.random.default_rng(5)
    f = rng.standard_normal((2,) + grid_shape)

    d = str(tmp_path / "snaps")
    with ps.ShardedSnapshot(d) as snap:
        snap.save(3, f=decomp.shard(f))
    out = str(tmp_path / "merged.h5")
    shapes = ps.ShardedSnapshot.merge(d, 3, out)
    assert shapes == {"f": f.shape}
    with h5py.File(out, "r") as g:
        assert np.array_equal(g["f"][...], f)

    # a missing region must raise (delete one shard dataset)
    with h5py.File(tmp_path / "snaps" / "shard-00000.h5", "a") as g:
        grp = g["step_0000000003/f"]
        del grp["shard0"]
    with pytest.raises(ValueError, match="missing|cover"):
        ps.ShardedSnapshot.merge(d, 3, str(tmp_path / "merged2.h5"))


def test_sharded_snapshot_refuses_mixed_runs(tmp_path):
    """Leftover shard files from a different run in the same directory
    must never be silently merged (ADVICE r4): conflicting run ids or
    per-array shape/dtype declarations raise."""
    d = str(tmp_path / "snaps")
    x = np.arange(8.0).reshape(2, 4)
    with ps.ShardedSnapshot(d, run_id="run-a") as snap:
        snap.save(1, x=x)
    # same id: loads fine
    assert np.array_equal(ps.ShardedSnapshot.load(d, 1)["x"], x)

    # a second file with a different run id
    import h5py
    with h5py.File(tmp_path / "snaps" / "shard-00099.h5", "w") as f:
        f.attrs["run_id"] = "run-b"
    with pytest.raises(ValueError, match="run ids"):
        ps.ShardedSnapshot.load(d, 1)

    # and (separately) a same-name array with a different declared shape
    d2 = str(tmp_path / "snaps2")
    with ps.ShardedSnapshot(d2) as snap:
        snap.save(1, x=x)
    with h5py.File(tmp_path / "snaps2" / "shard-00099.h5", "w") as f:
        g = f.create_group("step_0000000001/x")
        g.attrs["global_shape"] = np.array([4, 4], np.int64)
        ds = g.create_dataset("shard0", data=np.ones((4, 4)))
        ds.attrs["start"] = np.array([0, 0], np.int64)
    with pytest.raises(ValueError, match="different runs"):
        ps.ShardedSnapshot.load(d2, 1)


def test_sharded_snapshot_incomplete_raises(tmp_path):
    """A missing / partially-written host file must raise, never return
    uninitialized memory."""
    import h5py
    d = tmp_path / "snaps"
    d.mkdir()
    with h5py.File(d / "shard-00000.h5", "w") as f:
        g = f.create_group("step_0000000001/x")
        g.attrs["global_shape"] = np.array([4, 4], np.int64)
        ds = g.create_dataset("shard0", data=np.ones((2, 4)))
        ds.attrs["start"] = np.array([0, 0], np.int64)
    with pytest.raises(ValueError, match="covered"):
        ps.ShardedSnapshot.load(str(d), 1)


# -- OutputFile.output: rows appended through kept handles ------------------

def _plain_output(file, group, **kwargs):
    """The plain reference: upstream's append (output.py:157-181), one
    lookup, one ``resize`` and one ``dset[-1] = arr`` a key."""
    grp = file.create_group(group) if group not in file else file[group]
    for key, val in kwargs.items():
        arr = np.asarray(val)
        if key not in grp:
            grp.create_dataset(key, shape=(0,) + arr.shape,
                               maxshape=(None,) + arr.shape,
                               dtype=arr.dtype)
        dset = grp[key]
        dset.resize(dset.shape[0] + 1, axis=0)
        dset[-1] = arr


def _datasets(path):
    """``{name: (shape, maxshape, dtype, chunks, values)}`` of every
    dataset in the file."""
    import h5py
    found = {}

    def visit(name, obj):
        if isinstance(obj, h5py.Dataset):
            found[name] = (obj.shape, obj.maxshape, obj.dtype, obj.chunks,
                           obj[...])

    with h5py.File(path, "r") as f:
        f.visititems(visit)
    return found


def _assert_same_file(got_path, ref_path):
    got, ref = _datasets(got_path), _datasets(ref_path)
    assert list(got) == list(ref)
    for name, (shape, maxshape, dtype, chunks, values) in ref.items():
        assert got[name][:4] == (shape, maxshape, dtype, chunks), name
        assert np.array_equal(got[name][4], values, equal_nan=True), name


def _appends():
    return tuple(ps.obs.metrics.counter(c).value for c in (
        "output_appends", "output_appends_generic"))


def _row_values(kind, nrows=5):
    rng = np.random.default_rng(11)
    if kind == "float":
        return [float(x) for x in rng.standard_normal(nrows)]
    if kind == "0d":
        return [np.asarray(x) for x in rng.standard_normal(nrows)]
    if kind == "int":
        return [int(x) for x in rng.integers(-9, 9, nrows)]
    if kind == "f32_into_f64":
        # the first row makes the dataset float64; HDF5 widens the rest
        rows = rng.standard_normal((nrows, 2))
        return [rows[0]] + [r.astype(np.float32) for r in rows[1:]]
    if kind == "f64_into_f32":
        # ... and rounds a float64 row into a float32 dataset
        rows = rng.standard_normal((nrows, 2))
        return [rows[0].astype(np.float32)] + list(rows[1:])
    if kind == "device":
        import jax.numpy as jnp
        return [jnp.asarray(r, jnp.float32)
                for r in rng.standard_normal((nrows, 2))]
    if kind == "strided":
        return list(rng.standard_normal((nrows, 33, 2)).swapaxes(1, 2))
    return list(rng.standard_normal((nrows,) + kind))


@pytest.mark.parametrize("kind", [
    "float", "0d", (2,), (2, 33), (6, 33), "int", "f32_into_f64",
    "f64_into_f32", "device", "strided"], ids=str)
def test_output_file_rows_equal_plain_appends(kind, tmp_path):
    """Five rows of each kind the drivers write: the file equals, dataset
    for dataset, the one upstream's statements write, and after each
    key's first row no append takes the generic statements."""
    import h5py
    values = _row_values(kind)
    before = _appends()
    with ps.OutputFile(name=str(tmp_path / "got")) as out:
        for i, val in enumerate(values):
            out.output("energy", t=0.1 * i, x=val)
            out.output("statistics/f", x=val)
            # the row is in the library when output() returns
            assert out.file["energy/x"].shape[0] == i + 1
            dset = out.file["statistics/f/x"]
            assert np.array_equal(dset[i], np.asarray(val, dset.dtype))
    with h5py.File(tmp_path / "ref.h5", "w") as ref:
        for i, val in enumerate(values):
            _plain_output(ref, "energy", t=0.1 * i, x=val)
            _plain_output(ref, "statistics/f", x=val)
    _assert_same_file(tmp_path / "got.h5", tmp_path / "ref.h5")
    after = _appends()
    assert (after[0] - before[0], after[1] - before[1]) == (15, 0)


def test_output_file_reopened_appends_after_the_old_rows(tmp_path):
    """A file reopened in "a": the datasets found are taken as they are
    and the rows go on after their last one; a key the first session did
    not write is created."""
    import h5py
    name = str(tmp_path / "run")
    rows = np.random.default_rng(5).standard_normal((5, 2, 33))
    with ps.OutputFile(name=name) as out:
        for i in range(3):
            out.output("spectra", t=float(i), scalar=rows[i])
    before = _appends()
    with ps.OutputFile(name=name) as out:
        assert out.file["spectra/scalar"].shape == (3, 2, 33)
        for i in range(3, 5):
            out.output("spectra", t=float(i), scalar=rows[i], a=1.0 + i)
    assert _appends()[1] == before[1]
    with h5py.File(name + ".h5", "r") as f:
        assert np.array_equal(f["spectra/scalar"][...], rows)
        assert np.array_equal(f["spectra/t"][...], np.arange(5.0))
        assert np.array_equal(f["spectra/a"][...], [4.0, 5.0])
        assert f["spectra/scalar"].maxshape == (None, 2, 33)


@pytest.mark.parametrize("odd, raises", [
    (1.5, None),                         # h5py broadcasts a scalar
    (np.ones((1, 2)), None),             # ... and a leading unit axis
    (np.ones(3), TypeError),             # h5py: "Can't broadcast"
    (np.ones((2, 2)), TypeError),
], ids=["scalar", "unit_axis", "longer", "higher_rank"])
def test_output_file_row_of_another_shape_is_h5pys(odd, raises, tmp_path):
    """A value that has not the dataset's row shape goes through h5py's
    own ``resize`` and ``dset[-1] = arr``: its broadcast or its
    exception (and the row the resize left), counted as generic."""
    import h5py
    with ps.OutputFile(name=str(tmp_path / "got")) as out, \
            h5py.File(tmp_path / "ref.h5", "w") as ref:
        for write, generic in (
                (lambda **kw: out.output("g", **kw), 1),
                (lambda **kw: _plain_output(ref, "g", **kw), 0)):
            write(x=np.zeros(2), t=0.0)
            before = _appends()
            if raises is None:
                write(x=odd, t=1.0)
            else:
                with pytest.raises(raises, match="broadcast"):
                    write(x=odd, t=1.0)
            assert _appends()[1] - before[1] == generic
            write(x=np.ones(2), t=2.0)
    _assert_same_file(tmp_path / "got.h5", tmp_path / "ref.h5")


def test_output_file_follows_a_resize_through_the_file(tmp_path):
    """The number of rows is the dataset's own at every append: rows cut
    off or added through ``out.file`` between two outputs move where the
    next row lands, as they do for the plain statements."""
    import h5py
    with ps.OutputFile(name=str(tmp_path / "got")) as out, \
            h5py.File(tmp_path / "ref.h5", "w") as ref:
        for file, write in (
                (out.file, lambda **kw: out.output("g", **kw)),
                (ref, lambda **kw: _plain_output(ref, "g", **kw))):
            for i in range(4):
                write(x=np.full((2, 3), float(i)), t=float(i))
            file["g/x"].resize(2, axis=0)        # cut two rows off
            write(x=np.full((2, 3), 4.0), t=4.0)
            assert file["g/x"].shape == (3, 2, 3)
            assert file["g/t"].shape == (5,)
            file["g/t"].resize(8, axis=0)        # three rows of fill
            write(x=np.full((2, 3), 5.0), t=5.0)
            assert file["g/t"][-1] == 5.0 and file["g/t"].shape == (9,)
            assert np.array_equal(file["g/x"][:, 0, 0], [0, 1, 4, 5])
    _assert_same_file(tmp_path / "got.h5", tmp_path / "ref.h5")


def test_output_file_counts_appends_and_spans_a_call_once(tmp_path):
    """``output_appends`` counts keys x rows; the ``output_write`` span
    lies round the whole call, one row of the recorder a call."""
    before = _appends()
    with ps.OutputFile(name=str(tmp_path / "got")) as out, \
            ps.obs.recording() as spans:
        for i in range(4):
            out.output("energy", t=float(i), a=1.0, total=np.ones(2))
            out.output("statistics/f", t=float(i), mean=np.ones(2))
    assert [r[0] for r in spans] == ["output_write"] * 8
    after = _appends()
    assert (after[0] - before[0], after[1] - before[1]) == (4 * 5, 0)
    out.close()     # idempotent, handles dropped with the file
    assert not out._groups
