"""Launcher tests: env plumbing, per-rank logs, failure kill-all, elastic
restart. Children are plain python scripts (no jax init needed)."""

import os
import subprocess
import sys
import textwrap

import pytest

from paddle_tpu.distributed.launch.main import _parse, launch_procs


def _script(tmp_path, body):
    p = tmp_path / "train.py"
    p.write_text(textwrap.dedent(body))
    return str(p)


def _args(tmp_path, script, *extra):
    return _parse([*extra, "--log_dir", str(tmp_path / "log"), script])


class TestChipOwnership:
    """One process per chip (ISSUE 21): the launcher never steers
    children to the CPU unasked, and a chip subset carries the bounds
    libtpu needs."""

    def test_cpu_simulation_must_be_asked_for(self, tmp_path, monkeypatch):
        script = _script(tmp_path, "print('never runs')")
        monkeypatch.delenv("JAX_PLATFORMS")
        with pytest.raises(RuntimeError, match="JAX_PLATFORMS=cpu"):
            launch_procs(_args(tmp_path, script, "--nproc_per_node", "2"))
        assert not (tmp_path / "log" / "workerlog.0").exists()

    def test_devices_env(self, tmp_path, monkeypatch):
        script = _script(tmp_path, """
            import os
            print({k: v for k, v in os.environ.items() if k.startswith("TPU_")})
        """)
        assert launch_procs(_args(tmp_path, script, "--devices", "2")) == 0
        log = (tmp_path / "log" / "workerlog.0").read_text()
        for kv in ("'TPU_VISIBLE_DEVICES': '2'",
                   "'TPU_CHIPS_PER_PROCESS_BOUNDS': '1,1,1'",
                   "'TPU_PROCESS_BOUNDS': '1,1,1'"):
            assert kv in log
        monkeypatch.delenv("TPU_CHIPS_PER_PROCESS_BOUNDS", raising=False)
        with pytest.raises(RuntimeError, match="bounds"):
            launch_procs(_args(tmp_path, script, "--devices", "0,1"))


class TestLaunch:
    def test_single_proc_env_and_log(self, tmp_path):
        script = _script(tmp_path, """
            import os
            print("rank", os.environ["PADDLE_TRAINER_ID"],
                  "world", os.environ["PADDLE_TRAINERS_NUM"],
                  "master", os.environ["PADDLE_MASTER"])
        """)
        rc = launch_procs(_args(tmp_path, script))
        assert rc == 0
        log = (tmp_path / "log" / "workerlog.0").read_text()
        assert "rank 0 world 1" in log

    def test_multi_proc_ranks(self, tmp_path):
        script = _script(tmp_path, """
            import os
            print("R%s/%s" % (os.environ["PADDLE_TRAINER_ID"],
                              os.environ["PADDLE_DIST_NUM_PROCESSES"]))
        """)
        rc = launch_procs(_args(tmp_path, script, "--nproc_per_node", "3"))
        assert rc == 0
        logs = [(tmp_path / "log" / f"workerlog.{r}").read_text()
                for r in range(3)]
        for r in range(3):
            assert f"R{r}/3" in logs[r]

    def test_failure_propagates_and_kills_peers(self, tmp_path):
        script = _script(tmp_path, """
            import os, sys, time
            if os.environ["PADDLE_TRAINER_ID"] == "1":
                sys.exit(3)
            time.sleep(30)   # would time out unless killed by the launcher
        """)
        import time
        t0 = time.time()
        rc = launch_procs(_args(tmp_path, script, "--nproc_per_node", "2"))
        assert rc == 3
        assert time.time() - t0 < 25  # rank 0 was terminated, not waited out

    def test_elastic_restart_until_success(self, tmp_path):
        marker = tmp_path / "attempts"
        script = _script(tmp_path, f"""
            import os, sys
            p = {str(marker)!r}
            n = int(open(p).read()) if os.path.exists(p) else 0
            open(p, "w").write(str(n + 1))
            sys.exit(0 if n >= 2 else 1)   # succeed on the 3rd attempt
        """)
        rc = launch_procs(_args(tmp_path, script, "--max_restart", "3"))
        assert rc == 0
        assert marker.read_text() == "3"

    def test_elastic_exhausted(self, tmp_path):
        script = _script(tmp_path, "import sys; sys.exit(9)")
        rc = launch_procs(_args(tmp_path, script, "--max_restart", "1"))
        assert rc == 9

    def test_module_entrypoint(self, tmp_path):
        script = _script(tmp_path, "print('hello from child')")
        env = dict(os.environ)
        env.pop("PYTHONPATH", None)
        out = subprocess.run(
            [sys.executable, "-m", "paddle_tpu.distributed.launch",
             "--log_dir", str(tmp_path / "log"), script],
            cwd="/root/repo", env={**env, "PYTHONPATH": "/root/repo"},
            capture_output=True, timeout=120)
        assert out.returncode == 0
        assert "hello from child" in \
            (tmp_path / "log" / "workerlog.0").read_text()


class TestElasticDetection:
    def test_heartbeat_monitor_unit(self):
        """Worker stamps -> monitor sees it; stale stamp -> hung."""
        import time
        from paddle_tpu.distributed import elastic
        mon = elastic.HeartbeatMonitor("jobX")
        try:
            assert mon.hung_ranks([0, 1], ttl=0.2) == []  # never beat: quiet
            os.environ["PADDLE_JOB_ID"] = "jobX"
            t = elastic.start_heartbeat(store_addr=mon.addr, rank=0,
                                        interval=0.1)
            assert t is not None
            time.sleep(0.4)
            assert mon.last_beat(0) is not None
            assert mon.hung_ranks([0], ttl=5.0) == []
            elastic.stop_heartbeat()
            time.sleep(0.8)
            assert mon.hung_ranks([0], ttl=0.5) == [0]   # stamp went stale
            mon.clear(2)
            assert mon.last_beat(0) is None
        finally:
            elastic.stop_heartbeat()
            os.environ.pop("PADDLE_JOB_ID", None)
            mon.close()

    def test_stop_heartbeat_idempotent_and_joins(self):
        """Lifecycle contract: stop_heartbeat is idempotent, JOINS the
        beat thread (no stale stamp can race a restart), and a fresh
        start_heartbeat afterwards works."""
        import time
        from paddle_tpu.distributed import elastic
        mon = elastic.HeartbeatMonitor("jobLC")
        try:
            os.environ["PADDLE_JOB_ID"] = "jobLC"
            t = elastic.start_heartbeat(store_addr=mon.addr, rank=0,
                                        interval=0.1)
            assert t is not None and t.daemon  # cannot outlive the process
            # idempotent second start: no duplicate beat thread spawned
            assert elastic.start_heartbeat(store_addr=mon.addr) is None
            import threading as _th
            beats = [x for x in _th.enumerate()
                     if x.name == "elastic-heartbeat"]
            assert beats == [t], beats
            time.sleep(0.3)
            assert mon.last_beat(0) is not None
            elastic.stop_heartbeat()
            assert not t.is_alive()            # joined, not just signaled
            elastic.stop_heartbeat()           # idempotent: no raise
            elastic.stop_heartbeat()
            t2 = elastic.start_heartbeat(store_addr=mon.addr, rank=0,
                                         interval=0.1)
            assert t2 is not None and t2 is not t
            time.sleep(0.3)
            assert mon.last_beat(0) is not None
        finally:
            elastic.stop_heartbeat()
            os.environ.pop("PADDLE_JOB_ID", None)
            mon.close()

    def test_preemption_handler_flag_and_save_fn(self):
        """SIGTERM -> preempted() flips and the emergency save_fn runs
        (exit_code=None: poll-mode, the handler must NOT exit)."""
        import signal as sig
        import time
        from paddle_tpu.distributed import elastic
        ran = []
        try:
            elastic.install_preemption_handler(
                save_fn=lambda: ran.append(1), deadline=5.0, exit_code=None)
            assert not elastic.preempted()
            os.kill(os.getpid(), sig.SIGTERM)
            time.sleep(0.2)
            assert elastic.preempted()
            assert ran == [1]
        finally:
            elastic.uninstall_preemption_handler()
        assert not elastic.preempted()

    def test_hung_worker_detected_job_restarts_and_resumes(self, tmp_path):
        """The SURVEY §5 elastic contract end to end: rank 1 FREEZES (not
        crashes) mid-training; the launcher's heartbeat watchdog declares it
        hung, kills the job, restarts with a fresh rendezvous, and the
        script resumes from the distributed checkpoint and finishes."""
        import numpy as np
        ckpt_dir = tmp_path / "ckpt"
        ckpt_dir.mkdir()
        script = _script(tmp_path, f"""
            import os, sys, signal, time
            sys.path.insert(0, "/root/repo")
            os.environ["JAX_PLATFORMS"] = "cpu"
            import numpy as np
            rank = int(os.environ["PADDLE_TRAINER_ID"])
            rnd = int(os.environ["PADDLE_RESTART_ROUND"])
            from paddle_tpu.distributed.elastic import start_heartbeat
            start_heartbeat(interval=0.25)
            import paddle_tpu as paddle
            from paddle_tpu.distributed.checkpoint import (load_state_dict,
                                                           save_state_dict)
            ck = {str(ckpt_dir)!r}
            state = {{"w": paddle.to_tensor(np.zeros((3, 1), np.float32)),
                      "step": paddle.to_tensor(np.zeros((), np.float32))}}
            if os.path.exists(os.path.join(ck, "metadata.pkl")):
                load_state_dict(state, ck)
                open(os.path.join(ck, "resumed.%d" % rank), "w").write(
                    str(float(state["step"])))
            start = int(float(state["step"]))
            rng = np.random.RandomState(0)
            X = paddle.to_tensor(rng.randn(32, 3).astype("float32"))
            y = X.matmul(paddle.to_tensor(
                np.array([[1.5], [-2.0], [0.5]], np.float32)))
            wt = paddle.Parameter(state["w"].numpy())
            for step in range(start, 8):
                loss = ((X.matmul(wt) - y) ** 2).mean()
                loss.backward()
                wt.set_value(wt.numpy() - 0.1 * wt.grad.numpy())
                wt.clear_grad()
                if rank == 0:
                    save_state_dict(
                        {{"w": paddle.to_tensor(wt.numpy()),
                          "step": paddle.to_tensor(np.float32(step + 1))}},
                        ck)
                if rnd == 0 and rank == 1 and step == 3:
                    os.kill(os.getpid(), signal.SIGSTOP)   # freeze == hung
                time.sleep(0.05)
            final = float(((X.matmul(wt) - y) ** 2).mean())
            open(os.path.join(ck, "final.%d" % rank), "w").write(str(final))
        """)
        env_bak = dict(os.environ)
        os.environ.pop("PYTHONPATH", None)
        os.environ["PADDLE_HEARTBEAT_INTERVAL"] = "0.25"
        try:
            rc = launch_procs(_args(tmp_path, script, "--nproc_per_node", "2",
                                    "--max_restart", "2",
                                    "--elastic_timeout", "2.5"))
        finally:
            os.environ.clear()
            os.environ.update(env_bak)
        logs = [(tmp_path / "log" / f"workerlog.{r}").read_text()
                for r in range(2)]
        assert rc == 0, logs
        # the frozen rank resumed from a mid-training checkpoint on round 1
        assert (ckpt_dir / "resumed.1").exists(), logs
        assert float((ckpt_dir / "resumed.1").read_text()) >= 3
        # training CONTINUED: the resumed run finished and converged
        final = float((ckpt_dir / "final.1").read_text())
        assert np.isfinite(final) and final < 0.5, final


class TestLaunchDistributedInit:
    def test_two_process_collective(self, tmp_path):
        """End to end: the launcher's env contract drives
        init_parallel_env -> jax.distributed -> a real cross-process
        collective on the multi-process CPU backend (the reference's
        Gloo-on-localhost CI pattern, SURVEY §4)."""
        script = _script(tmp_path, """
            import os
            os.environ["JAX_PLATFORMS"] = "cpu"
            import sys
            sys.path.insert(0, "/root/repo")
            import jax
            jax.config.update("jax_platforms", "cpu")
            from paddle_tpu.distributed import init_parallel_env
            init_parallel_env()
            assert jax.process_count() == 2, jax.process_count()
            import jax.numpy as jnp
            from jax.experimental import multihost_utils
            total = multihost_utils.process_allgather(
                jnp.asarray([jax.process_index() + 1.0]))
            assert float(total.sum()) == 3.0, total  # 1 + 2
            print("COLLECTIVE_OK rank", jax.process_index())
        """)
        env_bak = dict(os.environ)
        os.environ.pop("PYTHONPATH", None)  # children must not grab the TPU
        try:
            rc = launch_procs(_args(tmp_path, script,
                                    "--nproc_per_node", "2"))
        finally:
            os.environ.clear()
            os.environ.update(env_bak)
        logs = [(tmp_path / "log" / f"workerlog.{r}").read_text()
                for r in range(2)]
        assert rc == 0, logs
        for r in range(2):
            assert "COLLECTIVE_OK" in logs[r], logs[r]


class TestElasticScaleIn:
    @pytest.mark.slow
    def test_2proc_loses_worker_restarts_as_1proc_and_resumes(self,
                                                              tmp_path):
        """r3 VERDICT #7 end to end: a 2-proc dp job loses rank 1 (crash);
        with --elastic_min_nprocs the launcher re-rendezvouses with the
        SURVIVING world size (1), and the script resumes from the
        distributed checkpoint — reshard-on-load across the topology
        change — and converges (ref: fleet/elastic/manager.py scale-in)."""
        import numpy as np
        ckpt_dir = tmp_path / "ckpt"
        ckpt_dir.mkdir()
        script = _script(tmp_path, f"""
            import os, sys, time
            sys.path.insert(0, "/root/repo")
            os.environ["JAX_PLATFORMS"] = "cpu"
            import numpy as np
            rank = int(os.environ["PADDLE_TRAINER_ID"])
            world = int(os.environ["PADDLE_TRAINERS_NUM"])
            rnd = int(os.environ["PADDLE_RESTART_ROUND"])
            import paddle_tpu as paddle
            from paddle_tpu.distributed.checkpoint import (load_state_dict,
                                                           save_state_dict)
            ck = {str(ckpt_dir)!r}
            state = {{"w": paddle.to_tensor(np.zeros((3, 1), np.float32)),
                      "step": paddle.to_tensor(np.zeros((), np.float32))}}
            if os.path.exists(os.path.join(ck, "metadata.pkl")):
                load_state_dict(state, ck)   # reshard-on-load: the ckpt was
                # written by the 2-proc round, read by the 1-proc round
                open(os.path.join(ck, "resumed.w%d.r%d" % (world, rank)),
                     "w").write(str(float(state["step"])))
            start = int(float(state["step"]))
            # dp data shard: each rank sees its slice; world=1 sees all
            rng = np.random.RandomState(0)
            Xall = rng.randn(32, 3).astype("float32")
            X = paddle.to_tensor(Xall[rank::world])
            y = X.matmul(paddle.to_tensor(
                np.array([[1.5], [-2.0], [0.5]], np.float32)))
            wt = paddle.Parameter(state["w"].numpy())
            for step in range(start, 10):
                loss = ((X.matmul(wt) - y) ** 2).mean()
                loss.backward()
                wt.set_value(wt.numpy() - 0.1 * wt.grad.numpy())
                wt.clear_grad()
                if rank == 0:
                    save_state_dict(
                        {{"w": paddle.to_tensor(wt.numpy()),
                          "step": paddle.to_tensor(np.float32(step + 1))}},
                        ck)
                    open(os.path.join(ck, "saved.%d" % (step + 1)),
                         "w").write("1")
                if rnd == 0 and rank == 1 and step == 3:
                    # die only once rank 0 has durably saved step >= 4, so
                    # the restart provably resumes mid-training (a plain
                    # step-3 exit races rank 0's save cadence)
                    while not os.path.exists(os.path.join(ck, "saved.4")):
                        time.sleep(0.05)
                    os._exit(17)          # rank 1 dies -> scale-in event
                if rnd == 0:
                    time.sleep(0.2)       # keep rank 0 mid-training so the
                    # kill-all lands before it finishes (no barrier in this
                    # toy script)
            final = float(((X.matmul(wt) - y) ** 2).mean())
            open(os.path.join(ck, "final.w%d.r%d" % (world, rank)),
                 "w").write(str(final))
        """)
        env_bak = dict(os.environ)
        os.environ.pop("PYTHONPATH", None)
        try:
            rc = launch_procs(_args(tmp_path, script, "--nproc_per_node",
                                    "2", "--max_restart", "2",
                                    "--elastic_min_nprocs", "1"))
        finally:
            os.environ.clear()
            os.environ.update(env_bak)
        log0 = (tmp_path / "log" / "workerlog.0").read_text()
        assert rc == 0, log0
        # round 1 ran at world=1 and RESUMED from the 2-proc checkpoint
        resumed = list(ckpt_dir.glob("resumed.w1.r0"))
        assert resumed, list(ckpt_dir.iterdir())
        assert float(resumed[0].read_text()) >= 3
        final = float((ckpt_dir / "final.w1.r0").read_text())
        assert np.isfinite(final) and final < 0.5, final
        # no 2-proc final: the original world never finished
        assert not list(ckpt_dir.glob("final.w2.*"))


class TestMultiProcessTrainingParity:
    @pytest.mark.slow
    def test_2proc_dp_training_loss_parity_vs_serial(self, tmp_path):
        """r3 VERDICT #10: launcher-driven 2-PROCESS dp training (real
        jax.distributed over the localhost rendezvous) reproduces the
        single-process loss trajectory exactly — closing the gap between
        'the collective works' and 'training works multi-process'
        (SURVEY §4 loss-parity-vs-serial oracle, test_dist_base pattern)."""
        import json
        import numpy as np
        out_dir = tmp_path / "out"
        out_dir.mkdir()
        body = f"""
            import os, sys, json
            sys.path.insert(0, "/root/repo")
            os.environ["JAX_PLATFORMS"] = "cpu"
            # one device per process: the parent test env carries the
            # 8-device virtual-mesh flag, which must not leak in
            os.environ["XLA_FLAGS"] = " ".join(
                f for f in os.environ.get("XLA_FLAGS", "").split()
                if "host_platform_device_count" not in f)
            import numpy as np
            import jax
            jax.config.update("jax_platforms", "cpu")
            jax.config.update("jax_default_matmul_precision", "highest")
            world_env = int(os.environ.get("PADDLE_TRAINERS_NUM", "1"))
            from paddle_tpu.distributed import init_parallel_env
            if world_env > 1:
                init_parallel_env()
            import jax.numpy as jnp
            from jax.sharding import (Mesh, NamedSharding,
                                      PartitionSpec as P)

            # tiny 2-layer MLP, pure-functional dp train loop: batch is
            # dp-sharded over the GLOBAL device mesh (2 procs x 1 dev);
            # GSPMD inserts the cross-process grad all-reduce
            devs = np.array(jax.devices())
            mesh = Mesh(devs.reshape(-1), ("dp",))
            rng = np.random.RandomState(0)
            W1 = jnp.asarray(rng.randn(4, 16).astype("float32") * 0.3)
            W2 = jnp.asarray(rng.randn(16, 1).astype("float32") * 0.3)
            X = rng.randn(8, 4).astype("float32")
            Y = (X @ rng.randn(4, 1)).astype("float32")

            def loss_fn(params, x, y):
                W1, W2 = params
                h = jnp.tanh(x @ W1)
                return (((h @ W2) - y) ** 2).mean()

            def step(params, x, y):
                l, g = jax.value_and_grad(loss_fn)(params, x, y)
                return [p - 0.1 * gg for p, gg in zip(params, g)], l

            jstep = jax.jit(step)
            bs = NamedSharding(mesh, P("dp"))
            from jax.experimental import multihost_utils
            if jax.process_count() > 1:
                Xg = multihost_utils.host_local_array_to_global_array(
                    X[jax.process_index()::2], mesh, P("dp"))
                Yg = multihost_utils.host_local_array_to_global_array(
                    Y[jax.process_index()::2], mesh, P("dp"))
            else:
                # serial oracle: SAME global batch ORDER as the dp run's
                # interleaved shards
                order = np.argsort(
                    np.arange(8).reshape(2, 4).T.reshape(-1), kind="stable")
                idx = np.concatenate([np.arange(0, 8, 2),
                                      np.arange(1, 8, 2)])
                Xg, Yg = jnp.asarray(X[idx]), jnp.asarray(Y[idx])
            params = [W1, W2]
            losses = []
            for _ in range(6):
                params, l = jstep(params, Xg, Yg)
                losses.append(float(l))
            if int(os.environ.get("PADDLE_TRAINER_ID", "0")) == 0:
                tag = "dp" if world_env > 1 else "serial"
                open(os.path.join({str(out_dir)!r}, tag + ".json"),
                     "w").write(json.dumps(losses))
        """
        script = _script(tmp_path, body)
        env_bak = dict(os.environ)
        os.environ.pop("PYTHONPATH", None)
        try:
            rc2 = launch_procs(_args(tmp_path, script,
                                     "--nproc_per_node", "2"))
            rc1 = launch_procs(_args(tmp_path, script,
                                     "--nproc_per_node", "1"))
        finally:
            os.environ.clear()
            os.environ.update(env_bak)
        logs = [(tmp_path / "log" / f"workerlog.{r}").read_text()
                for r in range(2)]
        assert rc2 == 0 and rc1 == 0, logs
        dp = json.loads((out_dir / "dp.json").read_text())
        serial = json.loads((out_dir / "serial.json").read_text())
        np.testing.assert_allclose(dp, serial, rtol=1e-5, atol=1e-6)
        assert dp[-1] < dp[0]    # and it actually trains


class TestElasticScaleOut:
    @pytest.mark.slow
    def test_1proc_scales_back_to_2proc_on_rejoin(self, tmp_path):
        """r4 VERDICT next #8, the mirror of scale-in: a job running BELOW
        its full world (here: started at 1 proc with
        --elastic_max_nprocs 2, i.e. capacity was short at launch) sees
        the rejoin signal, gracefully restarts, re-rendezvouses at 2
        procs, and RESUMES from the checkpoint across the topology change
        (reshard-on-load; ref: fleet/elastic/manager.py rejoin event)."""
        import numpy as np
        ckpt_dir = tmp_path / "ckpt"
        ckpt_dir.mkdir()
        rejoin = tmp_path / "rejoin.signal"
        script = _script(tmp_path, f"""
            import os, sys, time
            sys.path.insert(0, "/root/repo")
            os.environ["JAX_PLATFORMS"] = "cpu"
            import numpy as np
            rank = int(os.environ["PADDLE_TRAINER_ID"])
            world = int(os.environ["PADDLE_TRAINERS_NUM"])
            rnd = int(os.environ["PADDLE_RESTART_ROUND"])
            import paddle_tpu as paddle
            from paddle_tpu.distributed.checkpoint import (load_state_dict,
                                                           save_state_dict)
            ck = {str(ckpt_dir)!r}
            state = {{"w": paddle.to_tensor(np.zeros((3, 1), np.float32)),
                      "step": paddle.to_tensor(np.zeros((), np.float32))}}
            if os.path.exists(os.path.join(ck, "metadata.pkl")):
                load_state_dict(state, ck)
                open(os.path.join(ck, "resumed.w%d.r%d" % (world, rank)),
                     "w").write(str(float(state["step"])))
            start = int(float(state["step"]))
            rng = np.random.RandomState(0)
            Xall = rng.randn(32, 3).astype("float32")
            X = paddle.to_tensor(Xall[rank::world])
            y = X.matmul(paddle.to_tensor(
                np.array([[1.5], [-2.0], [0.5]], np.float32)))
            wt = paddle.Parameter(state["w"].numpy())
            for step in range(start, 10):
                loss = ((X.matmul(wt) - y) ** 2).mean()
                loss.backward()
                wt.set_value(wt.numpy() - 0.1 * wt.grad.numpy())
                wt.clear_grad()
                if rank == 0:
                    save_state_dict(
                        {{"w": paddle.to_tensor(wt.numpy()),
                          "step": paddle.to_tensor(np.float32(step + 1))}},
                        ck)
                if rnd == 0 and step == 3:
                    # capacity "returns": the infrastructure raises the
                    # rejoin signal; the WATCHER must interrupt this round
                    open({str(rejoin)!r}, "w").write("2")
                if rnd == 0:
                    time.sleep(0.3)    # stay mid-training so the watcher's
                    # graceful interrupt lands before the loop finishes
            final = float(((X.matmul(wt) - y) ** 2).mean())
            open(os.path.join(ck, "final.w%d.r%d" % (world, rank)),
                 "w").write(str(final))
        """)
        env_bak = dict(os.environ)
        os.environ.pop("PYTHONPATH", None)
        try:
            rc = launch_procs(_args(tmp_path, script, "--nproc_per_node",
                                    "1", "--max_restart", "2",
                                    "--elastic_max_nprocs", "2",
                                    "--elastic_rejoin_file", str(rejoin)))
        finally:
            os.environ.clear()
            os.environ.update(env_bak)
        log0 = (tmp_path / "log" / "workerlog.0").read_text()
        assert rc == 0, log0
        # round 1 ran at world=2 and RESUMED from the 1-proc checkpoint
        resumed = [p for p in ckpt_dir.glob("resumed.w2.r*")]
        assert len(resumed) == 2, list(ckpt_dir.iterdir())
        assert all(float(p.read_text()) >= 3 for p in resumed)
        for r in range(2):
            final = float((ckpt_dir / f"final.w2.r{r}").read_text())
            assert np.isfinite(final) and final < 0.5, final
