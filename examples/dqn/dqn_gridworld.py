#!/usr/bin/env python
"""DQN (reference example/dqn, shrunk to a 5x5 gridworld): epsilon-greedy
Q-learning with an experience-replay buffer and a frozen target network —
the imperative NDArray + executor workflow of the reference's
base.py/qnet, with no RL-framework dependency.

The agent starts anywhere, the goal is the corner; reward -1 per step,
+10 at the goal. A converged Q-net's greedy policy reaches the goal from
every start within the Manhattan-optimal step budget.
"""
import collections
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import numpy as np

import mxnet_tpu as mx

GRID = 5
ACTIONS = 4  # up/down/left/right
GAMMA = 0.9


def encode(pos):
    s = np.zeros((GRID * GRID,), np.float32)
    s[pos[0] * GRID + pos[1]] = 1.0
    return s


def step_env(pos, a):
    moves = [(-1, 0), (1, 0), (0, -1), (0, 1)]
    r, c = pos
    dr, dc = moves[a]
    r = min(max(r + dr, 0), GRID - 1)
    c = min(max(c + dc, 0), GRID - 1)
    new = (r, c)
    if new == (GRID - 1, GRID - 1):
        return new, 10.0, True
    return new, -1.0, False


def build_qnet():
    s = mx.sym.Variable("data")
    h = mx.sym.FullyConnected(s, num_hidden=64, name="fc1")
    h = mx.sym.Activation(h, act_type="relu")
    q = mx.sym.FullyConnected(h, num_hidden=ACTIONS, name="q")
    # LinearRegressionOutput against the TD target for the taken action
    return mx.sym.LinearRegressionOutput(
        data=q, label=mx.sym.Variable("target"), name="out")


def main(seed=0, episodes=250, batch=32):
    rng = np.random.RandomState(seed)
    net = build_qnet()
    exe = net.simple_bind(mx.cpu(), data=(batch, GRID * GRID),
                          target=(batch, ACTIONS))
    init = mx.init.Xavier()
    for name, arr in exe.arg_dict.items():
        if name not in ("data", "target"):
            init(name, arr)
    # frozen target network: a second executor, params copied periodically
    tgt = net.simple_bind(mx.cpu(), grad_req="null",
                          data=(batch, GRID * GRID),
                          target=(batch, ACTIONS))

    def sync_target():
        for name in exe.arg_dict:
            if name not in ("data", "target"):
                tgt.arg_dict[name][:] = exe.arg_dict[name].asnumpy()

    sync_target()
    updater = mx.optimizer.get_updater(
        mx.optimizer.create("adam", learning_rate=1e-2))
    replay = collections.deque(maxlen=4000)
    eps = 1.0

    def qvalues(states, executor):
        executor.arg_dict["data"][:] = states
        executor.arg_dict["target"][:] = np.zeros((batch, ACTIONS),
                                                  np.float32)
        return executor.forward()[0].asnumpy()

    for ep in range(episodes):
        pos = (rng.randint(GRID), rng.randint(GRID))
        for t in range(30):
            if rng.rand() < eps:
                a = rng.randint(ACTIONS)
            else:
                st = np.tile(encode(pos), (batch, 1))
                a = int(qvalues(st, exe)[0].argmax())
            new, r, done = step_env(pos, a)
            replay.append((encode(pos), a, r, encode(new), done))
            pos = new
            if done:
                break
        eps = max(0.05, eps * 0.99)

        # one batched TD update per episode
        if len(replay) >= batch:
            idx = rng.randint(0, len(replay), batch)
            s = np.stack([replay[i][0] for i in idx])
            a = np.array([replay[i][1] for i in idx])
            r = np.array([replay[i][2] for i in idx], np.float32)
            s2 = np.stack([replay[i][3] for i in idx])
            done = np.array([replay[i][4] for i in idx])
            q_now = qvalues(s, exe)
            q_next = qvalues(s2, tgt).max(axis=1)
            target = q_now.copy()
            target[np.arange(batch), a] = r + GAMMA * q_next * (~done)
            exe.arg_dict["data"][:] = s
            exe.arg_dict["target"][:] = target
            exe.forward(is_train=True)
            exe.backward()
            for i, name in enumerate(net.list_arguments()):
                if name in ("data", "target"):
                    continue
                updater(i, exe.grad_dict[name], exe.arg_dict[name])
        if ep % 20 == 0:
            sync_target()

    # greedy rollout from every start must reach the goal near-optimally
    failures = 0
    for r0 in range(GRID):
        for c0 in range(GRID):
            pos = (r0, c0)
            budget = 2 * (GRID - 1 - r0 + GRID - 1 - c0) + 2
            for t in range(max(budget, 1)):
                if pos == (GRID - 1, GRID - 1):
                    break
                st = np.tile(encode(pos), (batch, 1))
                pos, _, done = step_env(pos,
                                        int(qvalues(st, exe)[0].argmax()))
            if pos != (GRID - 1, GRID - 1):
                failures += 1
    print("greedy policy failures: %d / %d starts" % (failures, GRID * GRID))
    assert failures <= 2, failures
    print("DQN OK")


if __name__ == "__main__":
    main()
